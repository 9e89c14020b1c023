"""``decision_p99_ms.route`` in the cells without batching."""
from bench.harness import reader

read = reader("decision_p99_ms.route")
