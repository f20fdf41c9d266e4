"""chipbench: the on-chip benchmark of dynamo_tpu (see README.md)."""
