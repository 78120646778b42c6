"""The repo's benchmark: the yardstick later PRs are held to.

Everything here is the benchmark's own (see ``BENCHMARK.json`` ``paths``):
traffic generation, timing, FLOP arithmetic, the peaks table, the plain
float32 references, and the reduction from traces and counters to
metrics. From the program it takes only the system under test
(``magiattention_tpu.api``, ``models/llama.py``) and the counters that
program already keeps.
"""
