"""A share of the devices' operation time by the program's own scopes,
over every traced execution: the time under ``params["take"]`` over the
time under ``params["of"]``; each is a list of scope labels of the
family ``params["family"]``, ``"unscoped"`` or ``"ops"``. See
``benchmark/scope_time.py``."""

from benchmark import scope_time


def read(obs: dict, params: dict):
    table = scope_time.table(params["family"])
    if table is None:
        return None
    return scope_time.share_pct(table, params["take"], params["of"])
