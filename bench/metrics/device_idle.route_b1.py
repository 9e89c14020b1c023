"""``device_idle.route`` in the cells without batching."""
from bench.harness import reader

read = reader("device_idle.route")
