"""A fixed-size state a row beside a short paged K/V tail: the cache of a
stack of power-retention layers (``ModelConfig.retention``,
``ops/power_retention.py``).

A retention layer weighs key ``j`` by ``(q . k_j) ** 2`` decayed by a gate,
so everything a row's queries can still need of positions ``j < F`` is a
state ``[D, d]`` float32 a key-value head and its sum of keys ``[D]``,
whatever ``F`` is. The class holds, beside the parent's page pool and table:

* ``state [L, B, Hkv, D, d]`` and ``zsum [L, B, Hkv, D]`` float32, a ROW of
  the batch each (not a page: its size does not depend on the context), with
  ``g_fold [L, B, Hkv]``, the gate sum they are referenced to. Zeroed when a
  row is admitted (:meth:`reset_rows`), carried across the chunks of a
  chunked prefill, rebuilt from the tokens when a preempted session is
  admitted again.
* the parent's ``k_pages`` / ``v_pages [L, P, Hkv, PS, d]`` in the model's
  dtype and ``g_pages [L, P, Hkv, PS]`` float32, the gate sum of each
  position, for the positions NOT YET FOLDED, and ``g_last [L, B, Hkv]``, the
  gate sum of a row's last position. ``kv_quant="int8"`` stores the pages'
  keys and values int8 with a float32 scale a position and head
  (``ks_pages`` / ``vs_pages``) and reads them back in the model's dtype: a
  squared score doubles a key's rounding, so the cell serves the model's
  dtype and the int8 pages are the control a test holds the float32 ones
  apart from; the state is float32 in both.

**Where the fold lies.** Every dispatch ends with all of a row's FULL pages
folded: ``folded = lengths // PS * PS`` holds between dispatches and is no
field. A prefill dispatch attends its queries to the state as it found it,
to the at most ``PS - 1`` unfolded positions before it and to itself, and
folds up to the last page boundary it reaches; a fused decode window reads
the state, the ONE partly filled page and its write-behind tail pair by
pair, and folds that page once, in its flush, in the window that fills it
(a state written every step would double what a step reads of it; once a
window of 16 steps adds a sixteenth in the windows that fold). So a slot
before ``lengths // PS`` is never read again and the engine hands its page
to another row (``engine/engine.py``: the window pool's release rule at a
reach of one position), and a row holds the pages of one dispatch's writes
plus one. A probe whose pages are never recycled folds all the same: the
fold is this class's, on the device.

The class is made once a state shape by :func:`retention_cache_class`
(``FEATURE_DIM`` is the class's, as ``INDEX_DIM`` and ``LAYER_KINDS`` are
their classes'): whoever builds "a cache like this one" from ``k_pages``'
shape alone (the benchmark's probe) gets the state too, a row of it at
``batch = 1``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import struct

from ..ops import power_retention as pr
from ..ops.rotary import apply_rope
from .paged import PagedKVCache

F32 = jnp.float32
#: the per-row planes (batch axis 1, behind the layer axis)
ROW_FIELDS = ("state", "zsum", "g_fold", "g_last")


@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_rows(planes, row_mask):
    """The per-row planes with the masked rows zeroed, in place."""
    return tuple(
        jnp.where(row_mask.reshape(1, -1, *[1] * (p.ndim - 2)), 0, p)
        for p in planes
    )


class RetentionPagedKVCache(PagedKVCache):
    """:class:`PagedKVCache` for retention layers: see the module's note."""

    g_pages: jax.Array = None
    ks_pages: jax.Array = None      # float32 scales: the int8 form only
    vs_pages: jax.Array = None
    state: jax.Array = None
    zsum: jax.Array = None
    g_fold: jax.Array = None
    g_last: jax.Array = None
    # what the pages' keys and values are read back as (the model's dtype)
    dtype_name: str = struct.field(pytree_node=False, default="bfloat16")

    FEATURE_DIM = None      # D of the state, by the class
    POOL_FIELDS = ("k_pages", "v_pages", "g_pages")
    EPS = 1e-6              # the normaliser's epsilon, by the class
    FAMILY = "power retention (ModelConfig.retention)"

    BATCH_AXES = {
        "page_table": 0, "lengths": 0,
        "state": 1, "zsum": 1, "g_fold": 1, "g_last": 1,
    }
    LAYER_FIELDS = (*POOL_FIELDS, *ROW_FIELDS)
    SHARED_FIELDS = POOL_FIELDS
    PLANE_FIELDS = {"k": "k_pages", "v": "v_pages", "g": "g_pages"}

    @classmethod
    def create(cls, num_layers, batch, num_pages, page_size,
               max_pages_per_session, num_kv_heads, head_dim,
               dtype=jnp.bfloat16, use_kernel=False, use_ragged=False):
        if pr.feature_dim(head_dim) != cls.FEATURE_DIM:
            raise ValueError(
                f"{cls.__name__} keeps a state {cls.FEATURE_DIM} features "
                f"wide, which is not a head of {head_dim}'s "
                f"({pr.feature_dim(head_dim)})"
            )
        pool = (num_layers, num_pages, num_kv_heads, page_size, head_dim)
        rows = (num_layers, batch, num_kv_heads)
        int8 = "ks_pages" in cls.POOL_FIELDS
        return cls(
            k_pages=jnp.zeros(pool, jnp.int8 if int8 else dtype),
            v_pages=jnp.zeros(pool, jnp.int8 if int8 else dtype),
            g_pages=jnp.zeros(pool[:-1], F32),
            **({
                "ks_pages": jnp.zeros(pool[:-1], F32),
                "vs_pages": jnp.zeros(pool[:-1], F32),
            } if int8 else {}),
            dtype_name=jnp.dtype(dtype).name,
            state=jnp.zeros((*rows, cls.FEATURE_DIM, head_dim), F32),
            zsum=jnp.zeros((*rows, cls.FEATURE_DIM), F32),
            g_fold=jnp.zeros(rows, F32),
            g_last=jnp.zeros(rows, F32),
            page_table=jnp.zeros((batch, max_pages_per_session), jnp.int32),
            lengths=jnp.zeros((batch,), jnp.int32),
            page_size=page_size,
            use_kernel=use_kernel,
            use_ragged=use_ragged,
        )

    # -- the layer-state protocol ------------------------------------------------

    @property
    def layer_stacks(self):
        return tuple(getattr(self, f) for f in self.LAYER_FIELDS)

    def with_layer_stacks(self, *stacks):
        return self.replace(**dict(zip(self.LAYER_FIELDS, stacks)))

    @property
    def _pool(self):
        """The page planes, in ``POOL_FIELDS``' order."""
        return tuple(getattr(self, f) for f in self.POOL_FIELDS)

    def _stored(self, k, v, gsum):
        """Positions' keys, values and gate sums as the pages store them,
        in ``POOL_FIELDS``' order."""
        if "ks_pages" not in self.POOL_FIELDS:
            return k, v, gsum

        def int8(x):
            x = x.astype(F32)
            scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0
            return jnp.round(x / scale[..., None]).astype(jnp.int8), scale

        (kq, ks), (vq, vs) = int8(k), int8(v)
        return kq, vq, gsum, ks, vs

    def _loaded(self, planes):
        """``(k, v, gsum)`` of page planes gathered alike, keys and values
        in the model's dtype."""
        dtype = jnp.dtype(self.dtype_name)
        if len(planes) == 3:
            return planes[0].astype(dtype), planes[1].astype(dtype), planes[2]
        k, v, g, ks, vs = planes
        return (
            (k.astype(F32) * ks[..., None]).astype(dtype),
            (v.astype(F32) * vs[..., None]).astype(dtype), g,
        )

    @property
    def _sub(self) -> int:
        """Positions a step of the chunk form takes: whole pages."""
        ps = self.page_size
        return -(-pr.SUB_CHUNK // ps) * ps

    def _open_pages(self, reach: int = 1):
        """``(slots, pages) [B, reach]``: each row's partly filled page, the
        one of slot ``lengths // PS``, and the ``reach - 1`` behind it (the
        null page past the table: nothing of it is valid then)."""
        width = self.page_table.shape[1]
        slot = (self.lengths // self.page_size)[:, None] + jnp.arange(
            reach, dtype=jnp.int32
        )[None, :]
        page = jnp.take_along_axis(
            self.page_table, jnp.minimum(slot, width - 1), axis=1
        )
        return slot, jnp.where(slot < width, page, 0)

    #: the engine's rolling installs (``_flush_installs``) go to the one
    #: table: the rolling pool is the pool
    assign_window_pages_batch = PagedKVCache.assign_pages_batch

    def _scatter(self, pool, k_rot, v_new, gsum, q_pos, num_new):
        """Rotated keys, values and gate sums ``[B, S, Hkv(, d)]`` into one
        layer's page planes at the table's (page, offset) of each
        position."""
        b, s = gsum.shape[:2]
        page, off = self._slot_pages(q_pos, num_new)
        page, off = page.reshape(-1), off.reshape(-1)
        return tuple(
            plane.at[page, :, off].set(
                new.reshape(b * s, *new.shape[2:]).astype(plane.dtype),
                mode="drop",
            )
            for plane, new in zip(pool, self._stored(k_rot, v_new, gsum))
        )

    def attend(self, layer_state, q, k_new, v_new, rope, q_pos, num_new,
               sliding_window, attention_fn, scale=None, gate=None):
        """A dispatch of ``S`` positions a row (a prefill, a chunk of one,
        a one-token step): write them into the pages, attend
        (``retention_chunk``: the state as the dispatch found it, the open
        page, the dispatch itself), fold up to the last page boundary the
        row reaches. ``gate [B, S, Hkv]`` float32: the positions' log-gates.
        No softmax scale: it would multiply numerator and denominator
        alike."""
        *pool, st, zs, g_fold, g_last = layer_state
        b, s, hq, d = q.shape
        hkv, ps = k_new.shape[2], self.page_size
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        valid = jnp.arange(s, dtype=jnp.int32)[None, :] < num_new[:, None]
        gsum = g_last[:, None, :] + jnp.cumsum(
            jnp.where(valid[..., None], gate.astype(F32), 0.0), axis=1
        )
        # the open page as the dispatch found it, then the writes
        slot = self.lengths // ps
        page = self._open_pages()[1][:, 0]
        pk, pv, pg = self._loaded(tuple(p[page] for p in pool))
        pool = self._scatter(pool, k_rot, v_new, gsum, q_pos, num_new)
        # everything in the row's positions since its last fold: place e
        # holds position folded + e, the open page's r0 first
        sub = min(self._sub, -(-(ps + s) // ps) * ps)
        e = -(-(ps + s) // sub) * sub
        r0 = (self.lengths - slot * ps)[:, None]                   # [B, 1]
        place = jnp.arange(e, dtype=jnp.int32)[None, :]
        src = place - r0
        prior = place < r0
        valid_q = (src >= 0) & (src < num_new[:, None])
        at = jnp.clip(src, 0, s - 1)

        def ext(new, old):
            """``new [B, S, ...]`` at its places, ``old [B, Hkv, PS, ...]``
            (the open page, head-major) before it."""
            taken = jnp.take_along_axis(
                new, at.reshape(b, e, *[1] * (new.ndim - 2)), axis=1
            )
            if old is None:
                return taken
            old = jnp.moveaxis(old, 1, 2)                           # [B, PS, Hkv..]
            old = jnp.pad(old, ((0, 0), (0, e - ps), *[(0, 0)] * (old.ndim - 2)))
            return jnp.where(
                prior.reshape(b, e, *[1] * (new.ndim - 2)),
                old.astype(new.dtype), taken,
            )

        new_len = self.lengths + num_new
        fold = (prior | valid_q) & (
            place < (new_len // ps * ps - slot * ps)[:, None]
        )
        # the kernel takes steps of whole lane tiles, or one step
        chunk = (
            pr.power_retention_prefill
            if self.use_kernel and (sub % 128 == 0 or e == sub)
            else pr.retention_chunk
        )
        out, st, zs, g_fold = chunk(
            ext(q_rot, None).reshape(b, e, hkv, hq // hkv, d),
            ext(k_rot.astype(pk.dtype), pk), ext(v_new.astype(pv.dtype), pv),
            ext(gsum, pg), valid_q, prior | valid_q, fold,
            st, zs, g_fold, self.EPS, sub,
        )
        out = jnp.take_along_axis(
            out.reshape(b, e, hq, d),
            jnp.minimum(
                jnp.arange(s, dtype=jnp.int32)[None, :] + r0, e - 1
            )[:, :, None, None],
            axis=1,
        )
        g_last = jnp.min(
            jnp.where(valid[..., None], gsum, g_last[:, None, :]), axis=1
        )
        return out.astype(q.dtype), (*pool, st, zs, g_fold, g_last)

    # -- write-behind tail (fused multi-step decode) -----------------------------

    @property
    def tail_reads_whole_big(self) -> bool:
        """With the kernel the state passes whole, the layer's index beside
        it: a slice feeding a kernel copies the layer's state every step."""
        return self.use_kernel

    def tail_walk(self, k_steps, base_len, num_new):
        """The rows a window's sweep walks (``ops.power_retention.live_rows``:
        a row decodes from the first step or not at all)."""
        return pr.live_rows(num_new) if self.use_kernel else None

    def tail_big_stacks(self):
        """What a window reads and does not write: the state planes, and
        each row's open page of every layer, gathered once."""
        page = self._open_pages()[1][:, 0]
        return (
            self.state, self.zsum, self.g_fold, self.g_last,
            # [L, B, Hkv, PS(, d)]
            *self._loaded(tuple(jnp.take(p, page, axis=1) for p in self._pool)),
        )

    def tail_init(self, k_steps: int):
        l, _, hkv, _, d = self.k_pages.shape
        b = self.page_table.shape[0]
        z = jnp.zeros((l, b, hkv, k_steps, d), jnp.dtype(self.dtype_name))
        return (z, z, jnp.zeros((l, b, hkv, k_steps), F32))

    def tail_attend(self, big_state, tail_state, q, k_new, v_new, rope,
                    base_len, tail_len, step_idx, num_new, sliding_window,
                    scale=None, gate=None, walk=None):
        layer = None
        if self.use_kernel:
            *big_state, layer = big_state
        st, zs, g_fold, g_last, pk, pv, pg = big_state
        tk, tv, tg = tail_state                         # [B, Hkv, K(, d)]
        b, _, hq, d = q.shape
        hkv, ps, kk = tk.shape[1], self.page_size, tk.shape[2]
        q_rot = apply_rope(q, rope.cos, rope.sin)
        k_rot = apply_rope(k_new, rope.cos, rope.sin)
        # planes that are small are indexed here; the state goes whole
        idx, g_fold_l, g_last_l = None, g_fold, g_last
        if layer is not None:
            idx = jnp.reshape(layer, (1,))
            g_fold_l, g_last_l = g_fold[idx[0]], g_last[idx[0]]
            pk, pv, pg = pk[idx[0]], pv[idx[0]], pg[idx[0]]
        before = jnp.take_along_axis(
            tg, jnp.maximum(tail_len - 1, 0)[:, None, None], axis=2
        )[:, :, 0]
        g_now = jnp.where(
            (tail_len > 0)[:, None], before, g_last_l
        ) + gate[:, 0].astype(F32)                      # [B, Hkv]
        tk = jax.lax.dynamic_update_slice_in_dim(
            tk, jnp.moveaxis(k_rot, 1, 2).astype(tk.dtype), step_idx, axis=2
        )
        tv = jax.lax.dynamic_update_slice_in_dim(
            tv, jnp.moveaxis(v_new, 1, 2).astype(tv.dtype), step_idx, axis=2
        )
        tg = jax.lax.dynamic_update_slice_in_dim(
            tg, g_now[:, :, None], step_idx, axis=2
        )
        open_valid = (
            jnp.arange(ps, dtype=jnp.int32)[None, :]
            < (base_len - base_len // ps * ps)[:, None]
        )
        tail_valid = (
            jnp.arange(kk, dtype=jnp.int32)[None, :]
            < (tail_len + num_new)[:, None]
        )
        gs = jnp.concatenate([pg, tg], axis=2)          # [B, Hkv, PS + K]
        seen = jnp.concatenate([open_valid, tail_valid], axis=1)
        w = jnp.where(
            seen[:, None, :],
            jnp.exp(jnp.minimum(g_now[:, :, None] - gs, 0.0)), 0.0,
        )
        dec = jnp.exp(jnp.minimum(g_now - g_fold_l, 0.0))
        args = (
            q_rot[:, 0].reshape(b, hkv, hq // hkv, d),
            st, zs, dec,
            jnp.concatenate([pk, tk], axis=2),
            jnp.concatenate([pv, tv], axis=2), w, self.EPS,
        )
        if self.use_kernel:
            with jax.named_scope("retention_state"):
                out = pr.power_retention_decode(*args, layer=idx, walk=walk)
        else:
            out = pr.power_retention_decode_xla(*args)
        return out.reshape(b, 1, hq, d).astype(q.dtype), (tk, tv, tg)

    def tail_flush(self, tail, tail_len):
        """A window's positions into the pages (every layer at once), and
        the page a row filled in it into the row's state."""
        tk, tv, tg = tail                               # [L, B, Hkv, K(, d)]
        kk, ps = tk.shape[3], self.page_size
        q_pos = (
            self.lengths[:, None] + jnp.arange(kk, dtype=jnp.int32)[None, :]
        )
        pool = jax.vmap(
            lambda pool, k, v, g: self._scatter(
                pool, jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
                jnp.moveaxis(g, 1, 2), q_pos, tail_len,
            )
        )(self._pool, tk, tv, tg)
        last = jnp.take_along_axis(
            tg, jnp.maximum(tail_len - 1, 0)[None, :, None, None], axis=3
        )[..., 0]
        g_last = jnp.where((tail_len > 0)[None, :, None], last, self.g_last)
        new_len = self.lengths + tail_len
        flushed = self.replace(
            g_last=g_last, **dict(zip(self.POOL_FIELDS, pool))
        )
        # the pages a row has filled: from the one it had open, as many as
        # a window's positions can reach (one, where a page holds more
        # positions than a window writes)
        reach = (kk + ps - 2) // ps + 1
        slot, page = self._open_pages(reach)
        fold = jnp.repeat(slot < (new_len // ps)[:, None], ps, axis=1)

        def positions(plane):
            """Pages gathered ``[L, B, reach, Hkv, PS(, d)]`` as the rows'
            positions ``[L, B, reach * PS, Hkv(, d)]``."""
            got = jnp.moveaxis(plane, 3, 4)
            return got.reshape(*got.shape[:2], reach * ps, *got.shape[4:])

        filled = tuple(map(
            positions, self._loaded(tuple(p[:, page] for p in pool))
        ))

        planes = (self.state, self.zsum, self.g_fold)
        if self.use_kernel:
            with jax.named_scope("retention_fold"):
                state, zsum, g_fold = pr.power_retention_fold(
                    *planes, *filled, fold
                )
        else:
            def layer(at, planes):
                # in place in the carried planes: a scan's stacked outputs
                # would be a second copy of every layer's state
                one = pr.retention_fold(
                    *(p[at] for p in planes), *(f[at] for f in filled), fold
                )
                return tuple(
                    jax.lax.dynamic_update_index_in_dim(p, n, at, 0)
                    for p, n in zip(planes, one)
                )

            state, zsum, g_fold = jax.lax.fori_loop(
                0, self.state.shape[0], layer, planes
            )
        return flushed.replace(
            state=state, zsum=zsum, g_fold=g_fold, lengths=new_len
        )

    # -- rows --------------------------------------------------------------------

    def reset_rows(self, row_mask):
        """A row's table and length cleared and its state ZEROED: a session
        admitted into the row starts from no past."""
        planes = _zero_rows(
            tuple(getattr(self, f) for f in ROW_FIELDS), row_mask
        )
        return super().reset_rows(row_mask).replace(
            **dict(zip(ROW_FIELDS, planes))
        )

    def select_row(self, row):
        return super().select_row(row).replace(**{
            f: jax.lax.dynamic_slice_in_dim(getattr(self, f), row, 1, axis=1)
            for f in ROW_FIELDS
        })

    def merge_row(self, sub, row):
        return super().merge_row(sub, row).replace(**{
            f: jax.lax.dynamic_update_slice_in_dim(
                getattr(self, f), getattr(sub, f), row, axis=1
            )
            for f in ROW_FIELDS
        })

    def select_rows(self, rows):
        return super().select_rows(rows).replace(**{
            f: jnp.take(getattr(self, f), rows, axis=1, mode="clip")
            for f in ROW_FIELDS
        })

    def merge_rows(self, sub, rows):
        return super().merge_rows(sub, rows).replace(**{
            f: getattr(self, f).at[:, rows].set(getattr(sub, f), mode="drop")
            for f in ROW_FIELDS
        })

    # -- what a cache with a state does not do ---------------------------------------

    def _pages_only(self, what: str):
        raise NotImplementedError(
            f"{what} is not implemented for {self.FAMILY}: a row's past is "
            "a state and a few unfolded pages, not a run of pages that can "
            "be shipped, shared or reloaded"
        )

    def update_and_gather(self, *a, **kw):
        self._pages_only("gathering a row's keys and values")

    def _ingest_planes(self, planes, n_valid, first_slot=0):
        self._pages_only("ingesting a row's KV")

    def copy_page(self, dst, src):
        self._pages_only("a copy-on-write page split")

    def read_page(self, page):
        self._pages_only("reading a page for the spill store")

    def write_page(self, page, tiles):
        self._pages_only("reloading a spilled page")


def retention_cache_class(head_dim: int, eps: float, quantized: bool = False):
    return _cache_class(int(head_dim), float(eps), bool(quantized))


@functools.lru_cache(maxsize=None)
def _cache_class(head_dim: int, eps: float, quantized: bool):
    """THE retention cache class of a state shape (the feature width of a
    head of ``head_dim``), the normaliser's epsilon and the pages' stored
    form, made once (a class is a pytree node type: two engines of one shape
    must hold the same one)."""
    width = pr.feature_dim(head_dim)
    more = {}
    if quantized:
        pool = (*RetentionPagedKVCache.POOL_FIELDS, "ks_pages", "vs_pages")
        more = {
            "POOL_FIELDS": pool, "SHARED_FIELDS": pool,
            "LAYER_FIELDS": (*pool, *ROW_FIELDS),
            "PLANE_FIELDS": {
                **RetentionPagedKVCache.PLANE_FIELDS,
                "ks": "ks_pages", "vs": "vs_pages",
            },
        }
    return type(
        f"{'Quantized' if quantized else ''}RetentionPagedKVCache{width}",
        (RetentionPagedKVCache,),
        {"FEATURE_DIM": width, "EPS": float(eps), **more},
    )
