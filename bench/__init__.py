"""Chip benchmark of the serving paths: one cell per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that defines what is measured lives here and is found by
name: configurations (``configs/``), cells (``workloads/``), traffic
mixes (``traffic/``), per-layer metric readers (``metrics/``), FLOP and
byte counts (``ops/``), plain references (``reference/``) and the
peaks table (``peaks.json``).  The program (``repro``) is imported only
by the drivers (``drivers/``), to be driven.
"""
