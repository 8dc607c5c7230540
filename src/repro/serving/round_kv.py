"""Round-KV views: uniform slicing over the decode loop's two cache forms.

The decode loop hands ``store()`` either a dense cache (``k``/``v``
[L, N, S+G, KV, hd] — the legacy form, still used for SSM/hybrid
architectures and when ``paged_decode`` is off) or a paged one
(``pk``/``pv`` round pool [L, P, bt, KV, hd] plus the per-sequence page
table ``page_idx`` [N, nbt]). Policies extract block-aligned regions —
the history span, the output block, the prefill region — without caring
which form arrived: :func:`round_kv` wraps the cache in a view whose
``slice(lo, hi)`` returns the dense ``[L, N, hi-lo, KV, hd]`` rows for
exactly that region.

For the paged form a ``slice`` is an at-rest page gather — store-time
data movement of the same class as the segment entries it feeds, sized
to the region actually kept. The decode fast path itself never calls
``dense()`` (the full-cache oracle gather): that is pinned by the
monkeypatch-spy test in tests/test_paged_decode.py. A round cache may
run past the prompt and its generated tokens (decode runs at a bucketed
length), so policies slice the regions they keep.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax


@dataclass
class DenseRoundKV:
    """View over a dense round cache ``k``/``v`` [L, N, total, KV, hd]."""

    k: jax.Array
    v: jax.Array

    @property
    def total(self) -> int:
        return int(self.k.shape[2])

    def slice(self, lo: int, hi: int) -> Tuple[jax.Array, jax.Array]:
        return self.k[:, :, lo:hi], self.v[:, :, lo:hi]

    def dense(self) -> Tuple[jax.Array, jax.Array]:
        return self.k, self.v


@dataclass
class PagedRoundKV:
    """View over a paged round cache: pool [L, P, bt, KV, hd] + page
    table [N, nbt] (each agent's pages in dense order)."""

    pool_k: jax.Array
    pool_v: jax.Array
    page_idx: jax.Array      # [N, nbt] int32

    @property
    def bt(self) -> int:
        return int(self.pool_k.shape[2])

    @property
    def total(self) -> int:
        return int(self.page_idx.shape[1]) * self.bt

    def slice(self, lo: int, hi: int) -> Tuple[jax.Array, jax.Array]:
        """Gather [L, N, hi-lo, KV, hd] out of the pool: page rows
        ``lo//bt .. ceil(hi/bt)``, edge-trimmed for non-aligned bounds."""
        L, P, bt, KV, hd = self.pool_k.shape
        N, nbt = self.page_idx.shape
        p0, p1 = lo // bt, -(-hi // bt)
        rows = self.page_idx[:, p0:p1]               # [N, p1-p0]

        def gather(pool):
            x = pool[:, rows]                        # [L, N, p1-p0, bt, KV, hd]
            x = x.reshape(L, N, (p1 - p0) * bt, KV, hd)
            return x[:, :, lo - p0 * bt : hi - p0 * bt]

        return gather(self.pool_k), gather(self.pool_v)

    def dense(self) -> Tuple[jax.Array, jax.Array]:
        """Full dense [L, N, total, KV, hd] — the oracle gather. Never
        on the serving path (spy-pinned)."""
        return self.slice(0, self.total)


def round_kv(cache: dict):
    """Wrap a decode-loop cache in the matching view, or ``None`` when
    the cache carries no attention KV (SSM-only architectures)."""
    if "k" in cache:
        return DenseRoundKV(cache["k"], cache["v"])
    if "pk" in cache:
        return PagedRoundKV(cache["pk"], cache["pv"], cache["page_idx"])
    return None
