"""``routing_kernel_us.route`` in the cells without batching."""
from bench.harness import reader

read = reader("routing_kernel_us.route")
