"""Shared-filesystem spill/warm-start for prefix KV pages.

One store = one directory of ``<store_digest>-<chain_digest>.pfx``
files, each a pickled dict: the chain digest, the parent's digest
(``keys.ROOT`` at depth 0), the depth, and the page's K/V rows as
host numpy arrays keyed by flattened cache-tree path. The chain
digest is the same token-prefix digest the in-pool cache and the
router hash (``keys``); ``store_digest`` scopes every entry by what
makes pages interchangeable across replicas — model config, kv page
geometry + dtype, jax version, device kind — so a lever change is a
clean MISS, never stale K/V.

Commit discipline is ``tpunet.utils.fsatomic``: content-digest tmp +
rename under a flock-guarded first-writer-wins check, exactly the
shared-filesystem story the AOT program store proved. N replicas
spilling the same fleet-common system prefix write it once.

``save`` is write-through at insert time and best-effort (a read-only
disk degrades to a per-replica cache, never a crash); ``load_all``
yields entries sorted by depth so a warming replica can insert each
page only after its parent landed (capacity may truncate a chain —
depth order guarantees the kept prefix is still prefix-closed).
"""

from __future__ import annotations

import glob
import os
import pickle
from typing import Iterator, Optional

from tpunet.utils import fsatomic

SUFFIX = ".pfx"


class PrefixStore:
    def __init__(self, directory: str, store_digest: str):
        self.directory = directory
        self.store_digest = store_digest

    def _path(self, chain_digest: str) -> str:
        return os.path.join(
            self.directory,
            f"{self.store_digest}-{chain_digest}{SUFFIX}")

    def exists(self, chain_digest: str) -> bool:
        return os.path.exists(self._path(chain_digest))

    def save(self, chain_digest: str, parent_digest: str, depth: int,
             rows: dict) -> bool:
        """Publish one page's rows (host numpy arrays keyed by
        flattened tree path). First writer wins; an existing entry is
        never rewritten. False on any OS failure."""
        payload = pickle.dumps({
            "digest": chain_digest,
            "parent": parent_digest,
            "depth": int(depth),
            "rows": rows,
        })
        try:
            return fsatomic.publish_bytes(self._path(chain_digest),
                                          payload)
        except OSError:
            return False

    def load_all(self, limit: Optional[int] = None) -> Iterator[dict]:
        """Entries for THIS store digest, shallowest first (parents
        before children), corrupt/foreign files skipped. ``limit``
        bounds how many are even read — warm-start is capacity-bound
        anyway."""
        pattern = os.path.join(self.directory,
                               self.store_digest + "-*" + SUFFIX)
        entries = []
        for path in sorted(glob.glob(pattern)):
            try:
                with open(path, "rb") as f:
                    entry = pickle.load(f)
                entries.append(entry)
            except Exception:  # noqa: BLE001 — torn/foreign file:
                continue       # warm-start is best-effort.
        entries.sort(key=lambda e: int(e.get("depth", 0)))
        if limit is not None:
            entries = entries[:limit]
        return iter(entries)


def build_prefix_store(directory: str, model_cfg,
                       serve_cfg) -> PrefixStore:
    """A store scoped by everything that makes a spilled page safe to
    map into THIS engine's pool: the full model config, the kv page
    geometry and dtype, and the runtime (jax version + device kind —
    quantization rounding may differ across backends)."""
    import dataclasses

    import jax

    from tpunet.utils.cache import AotProgramStore

    digest = AotProgramStore.digest({
        "model": dataclasses.asdict(model_cfg),
        "kv_page_tokens": serve_cfg.kv_page_tokens,
        "kv_dtype": serve_cfg.kv_dtype,
        # how a page's rows are stored (models/vit.py
        # _paged_decode_attend): a token's heads side by side, columns
        # rounded up to the lane tile. A store spilled as
        # [page_tokens, H, D] rows is another store.
        "kv_row_layout": "heads_flat_lane_padded",
        "jax": jax.__version__,
        "device_kind": jax.devices()[0].device_kind,
    })
    return PrefixStore(directory, digest)
