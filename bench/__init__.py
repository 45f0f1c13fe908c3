"""The chip benchmark: cells, traffic, references and trace reduction.

Everything the benchmark measures with lives here.  Of the program it
imports only the system under test: the front door and the problem
definitions in the configuration modules' `solver`, and the local mesh in
`bench/harness.py`.  The entry point is `bench/run.py`.
"""
