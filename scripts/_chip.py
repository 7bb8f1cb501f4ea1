"""Device gate shared by the TPU measurement scripts.

A measurement path that finds no chip fails; it does not fall back to
the CPU. Every record a script prints carries what ``require_tpu``
returns, so a number can always be traced to the device it came from.
"""

from __future__ import annotations

import sys


def device_record() -> dict:
    """What every record carries: the device the numbers came from."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": jax.device_count()}


def require_tpu(*peak_tables) -> dict:
    """``device_record()`` of a TPU whose kind is in every one of
    ``peak_tables`` (``((substring, peak), ...)``); on anything else,
    a note on stderr and exit code 2."""
    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"# {sys.argv[0]} measures the TPU; this is {dev}",
              file=sys.stderr)
        sys.exit(2)
    kind = dev["device_kind"].lower()
    if not all(any(k in kind for k, _ in table) for table in peak_tables):
        print(f"# device_kind {dev['device_kind']!r} is not in "
              f"{sys.argv[0]}'s peak tables; add it with its source "
              "before measuring on it", file=sys.stderr)
        sys.exit(2)
    return dev
