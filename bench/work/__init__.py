"""Least work of one training step, counted from shapes, one module per
model kind. What the mathematics needs, not what today's kernels do, so a
kernel that does more work than needed reads low and no implementation can
read over its roofline. Transcendentals are counted apart and never as
FLOPs: the chip has no published peak for them."""
