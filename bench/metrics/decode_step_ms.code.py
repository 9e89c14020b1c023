"""Host time of one ``ServingEngine.step`` of the MoE cell (decode step,
tokens read back), as ``decode_step_ms.serve`` reads it (ms)."""
from bench.harness import reader

read = reader("decode_step_ms.serve")
