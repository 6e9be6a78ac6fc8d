"""The chip benchmark: ``python bench/run.py --workload <cell> ...``
(see ``bench/run.py`` and ``BENCHMARK.json``)."""
