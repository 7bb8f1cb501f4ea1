"""Device time per execution of a program, in ms, under some of the
program's own scopes: the median over the traced executions of the
programs whose label matches ``params["program"]``, of the operations'
time under the scopes ``params["take"]`` of the family
``params["family"]`` — or, with ``"take": "device"``, of the whole
execution (its ``XLA Modules`` event). See ``benchmark/scope_time.py``."""

from benchmark import scope_time


def read(obs: dict, params: dict):
    table = scope_time.table(params["family"])
    if table is None:
        return None
    return scope_time.median_ms(table, params["program"], params["take"])
