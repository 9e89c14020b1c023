"""``flush_ms.route`` in the cells without batching."""
from bench.harness import reader

read = reader("flush_ms.route")
