"""The benchmark: harness, traffic, references and the trace reduction.

Everything under this directory is the yardstick. It calls the program
(``tpunet``) only as the system under test; nothing here is imported by
the program. See ``benchmark/README.md``.
"""
