"""The chip benchmark of the APC solver: one command runs one cell once
(``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>``).  See ``harness.py`` for how its files are found by name."""
