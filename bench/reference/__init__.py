"""Plain float32 references, one module per model family (``dense.py``).

A family module provides the seeded weights the benchmark serves
(``weight_specs`` + ``make_weights``), its own quantizer with exact
dequantization (``quantize_on_grid``) and the forward pass (``logits_at``).  It imports nothing of the program
under test.
"""
