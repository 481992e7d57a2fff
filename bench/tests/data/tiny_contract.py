"""Test-only reference: the dense decoder's, declaring a leaf role of its
own (the MLP's norm drawn as a shift, near 0, where the default draws a gain
near 1)."""
from bench.configs.dense_decoder import hyper, logits  # noqa: F401

LEAF_ROLES = {"ln2": "bias"}
