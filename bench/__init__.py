"""The on-chip benchmark: ``python bench/run.py --workload <cell> ...``
(see ``bench/run.py`` and ``PERF.md``)."""
