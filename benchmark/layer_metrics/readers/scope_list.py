"""Device time under scopes that the metric itself lists.

``params["scopes"]`` is ``[[label, regular expression], ...]`` over an
operation's ``op_name`` path, the first match wins — the form
``tpunet.obs.device_time.device_time_by_scope`` takes; the readers
``scope_ms`` / ``scope_share_pct`` take theirs from a family of
``benchmark/scopes.json`` instead. Two readings:

- ``params["program"]`` and ``params["take"]``: the median over the
  traced executions of the programs whose label matches ``program`` of
  the device time, in ms, under the scope labels ``take``;
- ``params["take"]`` and ``params["of"]`` without ``program``: over every
  execution, the time under ``take`` as a share (%) of the time under
  ``of`` (labels, ``"unscoped"`` or ``"ops"``).

``None`` — the metric is left out — without a trace (``--trace 0``, a
rehearsal), or where the program has no scope table or none of these
programs. One table per scope list and process: the metrics of one
cell share their list.
"""

import functools
import json

from benchmark import harness, scope_time


@functools.lru_cache(maxsize=None)
def _table(scopes_json: str):
    path = scope_time.xplane_path()
    if path is None:
        return None
    try:
        from tpunet.obs import device_time
        texts = device_time.program_texts()
        if not texts:
            return None
        scopes = [tuple(pair) for pair in json.loads(scopes_json)]
        tab = device_time.device_time_by_scope(path, texts, scopes)
    except Exception as e:  # noqa: BLE001 — a metric left out, not a run lost
        harness.say(f"scope table of a metric's own list not read: {e!r}")
        return None
    for line in scope_time.describe(tab, device_time.classifier(scopes)):
        harness.say(line)
    return tab


def read(obs: dict, params: dict):
    tab = _table(json.dumps(params["scopes"]))
    if tab is None:
        return None
    if "program" in params:
        return scope_time.median_ms(tab, params["program"], params["take"])
    return scope_time.share_pct(tab, params["take"], params["of"])
