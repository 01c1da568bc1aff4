"""Plain float32 references: one module per model family (``init`` and
``forward``), and ``gate`` for DART's difficulty and exit gate."""
