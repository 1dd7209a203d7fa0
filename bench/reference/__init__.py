"""Plain references, one module per model kind; see common.py."""
