"""On-chip benchmark of the positive-feature Sinkhorn solver.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the accelerator it is started on.
Everything a cell needs is found by name: ``workloads/<cell>.json`` names its
configuration (``configs/<name>.json``) and its driver (``drivers/<kind>.py``);
each per-layer metric is read by ``metrics/<name>.py``; the plain reference a
configuration is checked against is ``references/<name>.py``.
"""
