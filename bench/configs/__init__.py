"""Benchmark configurations, one module and one JSON file each, found by name.

A module gives `CONFIG` (its JSON), `make_inputs`, `solver`, `control`,
`check` and `work`; see `bench/configs/lorenz_sweep.py` for what each does.
"""
