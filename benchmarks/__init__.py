"""CPU benchmark harness: one module per paper figure or table."""
