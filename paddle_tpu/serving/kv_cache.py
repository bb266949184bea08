"""Donated paged KV cache + single-token GPT decode step.

The ``gpt_causal`` decode serving path cannot ride the bucketized batch
server: each generated token would re-attend the whole prefix through a
fresh full-context dispatch (O(T²) per token) and every sequence length
would be a new shape.  Instead the decode engine keeps per-layer K/V pools
of FIXED-SIZE pages (``[L, n_pages, page_len, H, Dh]``), gives each
in-flight request a slot with a page LIST (grown a page at a time, freed
on completion), and jit-compiles ONE step function over the fixed
``[slots]`` batch — requests join and leave the batch between iterations
by flipping their slot's active flag, with no recompile ever.  The pools
are DONATED to each step (``donate_argnums``), so on TPU the update
aliases the input buffers in place; page 0 is a reserved scratch page that
inactive slots write into, keeping the scatter shape static.

The step math mirrors ``models/transformer.build_gpt_pretrain`` op by op
(arange positions, pre-encoder LN, fused-QKV post-LN blocks, erf-gelu FFN,
f32 LN/softmax stats) so the engine's logits match the training program's
within float tolerance — regression-tested against the full-context
program in tests/test_serving.py.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor

KV_PAGES_GAUGE = _monitor.REGISTRY.gauge(
    "paddle_tpu_serving_kv_pages_in_use",
    "KV-cache pages currently owned by in-flight decode requests "
    "(page 0, the inactive-slot scratch page, is never owned)")
KV_ALLOC_CTR = _monitor.REGISTRY.counter(
    "paddle_tpu_serving_kv_page_events_total",
    "KV page pool events", ("event",))
_ALLOC = KV_ALLOC_CTR.labels(event="alloc")
_FREE = KV_ALLOC_CTR.labels(event="free")
_EXHAUSTED = KV_ALLOC_CTR.labels(event="exhausted")


class PagedKVCache:
    """Fixed-size page pool for one decode engine.

    Host side: a free-page list and per-slot page lists (``alloc_page`` /
    ``free_slot``).  Device side: the stacked K/V pools the jitted step
    donates and returns.  Page 0 is reserved scratch — inactive slots'
    writes land there, so the step's scatter indices never change shape.
    """

    def __init__(self, n_layers: int, n_pages: int, page_len: int,
                 n_head: int, d_head: int, max_slots: int,
                 dtype=jnp.float32):
        if n_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved scratch)")
        self.n_layers = int(n_layers)
        self.n_pages = int(n_pages)
        self.page_len = int(page_len)
        self.max_slots = int(max_slots)
        shape = (n_layers, n_pages, page_len, n_head, d_head)
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        self._mu = threading.Lock()
        self._free: List[int] = list(range(1, n_pages))  # guarded-by: _mu
        self._owned: Dict[int, List[int]] = {}  # guarded-by: _mu
        # the pool's device bytes are attributed to the HBM accountant's
        # kv_pages class (weak registration — telemetry must not keep a
        # dead engine's pools alive)
        from .. import hbm as _hbm
        _hbm.register_kv_pool(self)

    def alloc_page(self, slot: int) -> Optional[int]:
        """Grant ``slot`` one more page; None when the pool is exhausted
        (the caller parks the request until a completion frees pages)."""
        with self._mu:
            if not self._free:
                _EXHAUSTED.inc()
                return None
            page = self._free.pop()
            self._owned.setdefault(slot, []).append(page)
            in_use = self.n_pages - 1 - len(self._free)
        _ALLOC.inc()
        KV_PAGES_GAUGE.set(in_use)
        return page

    def free_slot(self, slot: int) -> int:
        """Return every page ``slot`` owns to the pool (request complete);
        returns how many were freed.  The page CONTENTS are not cleared —
        the next owner overwrites positions before attending them, and
        the attention mask hides everything past the written prefix."""
        with self._mu:
            pages = self._owned.pop(slot, [])
            self._free.extend(pages)
            in_use = self.n_pages - 1 - len(self._free)
        if pages:
            _FREE.inc(len(pages))
            KV_PAGES_GAUGE.set(in_use)
        return len(pages)

    def pages_in_use(self) -> int:
        with self._mu:
            return self.n_pages - 1 - len(self._free)

    def buffers_alive(self) -> bool:
        """False when a failed donated step consumed the pools (the
        arguments were donated to a call that died mid-execution)."""
        k = self.k
        return not (hasattr(k, "is_deleted") and k.is_deleted())

    def reinit_pools(self) -> None:
        """Fresh zero pools after a failed donated step poisoned the old
        buffers (shape/dtype metadata survives deletion).  Cached
        prefixes are gone, so the caller must fail every in-flight
        request first; page bookkeeping stays valid."""
        shape, dtype = self.k.shape, self.k.dtype
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)

    def pages_of(self, slot: int) -> List[int]:
        with self._mu:
            return list(self._owned.get(slot, []))

    def pool_bytes(self) -> int:
        """Device bytes of the K/V pools (both stacks) — the resident
        cost of the cache regardless of page occupancy."""
        return (int(getattr(self.k, "nbytes", 0) or 0)
                + int(getattr(self.v, "nbytes", 0) or 0))


def params_from_scope(scope, cfg) -> Dict[str, jnp.ndarray]:
    """Pull the GPT parameter set (models/transformer naming) out of a
    scope holding a trained/initialized ``build_gpt_pretrain`` model."""
    names = ["word_embedding", "pos_embedding", "pre_encoder.ln.w",
             "pre_encoder.ln.b", "lm_out.w", "lm_out.b"]
    for i in range(cfg.n_layer):
        p = f"enc_{i}"
        names += [f"{p}.attn.qkv.w", f"{p}.attn.qkv.b",
                  f"{p}.attn.out.w", f"{p}.attn.out.b",
                  f"{p}.ln1.w", f"{p}.ln1.b",
                  f"{p}.ffn.fc1.w", f"{p}.ffn.fc1.b",
                  f"{p}.ffn.fc2.w", f"{p}.ffn.fc2.b",
                  f"{p}.ln2.w", f"{p}.ln2.b"]
    params = {}
    for n in names:
        v = scope.find_var(n)
        if v is None:
            raise KeyError(
                f"GPT decode param {n!r} missing from scope — build the "
                "model with models.transformer.build_gpt_pretrain and run "
                "the startup program first")
        params[n] = jnp.asarray(v)
    return params


def _layer_norm(x, w, b, eps=1e-5):
    # mirrors ops/nn_ops._layer_norm: stats in f32, affine in x dtype
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.var(xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(v + eps)
    y = (x - m.astype(x.dtype)) * inv.astype(x.dtype)
    return (y * w.astype(y.dtype) + b.astype(y.dtype)).astype(x.dtype)


def _part(name: str):
    """``pt.decode/<part>``: the scope a part of the decode step is traced
    under, so its device operations carry the part's name in their HLO
    metadata (the layer index is not in the name: the device trace is read
    as per-part sums by ``benchmark/op_scopes.py``)."""
    return jax.named_scope("pt.decode/" + name)


class GPTDecodeModel:
    """One-token-per-slot decode step over the paged cache, jitted once.

    ``step(params, k, v, ids, pos, page_table, active)`` processes the
    current token of every slot: writes this position's K/V into the
    slot's page, attends the slot's whole cached prefix (pages gathered
    by the table, positions past ``pos`` masked), and returns the
    next-token logits.  All shapes are fixed by (max_slots, max_pages,
    page_len), so the first call traces+compiles and every later call —
    whatever mix of requests occupies the slots — is a cache hit
    (``trace_count`` stays flat; asserted in tests).  K/V pools are
    donated: argument buffers are reused for the results on backends
    that support donation.
    """

    def __init__(self, cfg, page_len: int, max_pages: int):
        self.cfg = cfg
        self.page_len = int(page_len)
        self.max_pages = int(max_pages)
        self.n_head = cfg.n_head
        self.d_head = cfg.d_model // cfg.n_head
        self.trace_count = 0
        self._step = jax.jit(self._step_impl, donate_argnums=(1, 2))

    def kv_shape(self, n_pages: int):
        return (self.cfg.n_layer, n_pages, self.page_len, self.n_head,
                self.d_head)

    def step(self, params, k, v, ids, pos, page_table, active):
        """ids/pos/active: [S] int32/bool; page_table: [S, max_pages]
        int32 (unallocated entries 0 — masked off by ``pos``).
        Returns (logits [S, vocab], new_k, new_v)."""
        return self._step(params, k, v, jnp.asarray(ids, jnp.int32),
                          jnp.asarray(pos, jnp.int32),
                          jnp.asarray(page_table, jnp.int32),
                          jnp.asarray(active, bool))

    def _step_impl(self, params, k, v, ids, pos, page_table, active):
        # python side effect on purpose: runs only while TRACING, so the
        # counter counts compiles — the "no per-request recompile" gate
        self.trace_count += 1
        cfg = self.cfg
        S = ids.shape[0]
        H, Dh, D = self.n_head, self.d_head, cfg.d_model
        PL, MP = self.page_len, self.max_pages
        T = MP * PL                      # max attended context per slot
        scale = float(Dh) ** -0.5

        with _part("embed"):
            x = params["word_embedding"][ids] + params["pos_embedding"][pos]
            x = _layer_norm(x, params["pre_encoder.ln.w"],
                            params["pre_encoder.ln.b"])

        with _part("kv_write"):
            # this token's write target: (page, offset) per slot; inactive
            # slots are routed to scratch page 0 so the scatter stays dense
            page_idx = pos // PL
            offset = pos % PL
            cur_page = jnp.take_along_axis(
                page_table, page_idx[:, None], axis=1)[:, 0]
            cur_page = jnp.where(active, cur_page, 0)

        with _part("attention"):
            # context mask: position t of the gathered pages is attendable
            # iff t <= pos (page-table order IS position order)
            t_idx = jnp.arange(T)
            attend = t_idx[None, :] <= pos[:, None]          # [S, T]
            neg = jnp.asarray(-1e9, x.dtype)

        for i in range(cfg.n_layer):
            p = f"enc_{i}"
            with _part("qkv"):
                qkv = x @ params[f"{p}.attn.qkv.w"] + \
                    params[f"{p}.attn.qkv.b"]
                q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
                q = q.reshape(S, H, Dh)
                k_new = k_new.reshape(S, H, Dh)
                v_new = v_new.reshape(S, H, Dh)
            with _part("kv_write"):
                k = k.at[i, cur_page, offset].set(k_new)
                v = v.at[i, cur_page, offset].set(v_new)
            with _part("kv_gather"):
                # this slot's prefix: [S, MP, PL, H, Dh] -> [S, T, H, Dh]
                kp = k[i][page_table].reshape(S, T, H, Dh)
                vp = v[i][page_table].reshape(S, T, H, Dh)
            with _part("attention"):
                scores = jnp.einsum("shd,sthd->sht", q, kp) * scale
                scores = jnp.where(attend[:, None, :], scores, neg)
                w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
                w = w.astype(x.dtype)
                ctx = jnp.einsum("sht,sthd->shd", w, vp).reshape(S, D)
            with _part("attn_out"):
                attn = ctx @ params[f"{p}.attn.out.w"] + \
                    params[f"{p}.attn.out.b"]
                x = _layer_norm(x + attn, params[f"{p}.ln1.w"],
                                params[f"{p}.ln1.b"])
            with _part("ffn"):
                h = x @ params[f"{p}.ffn.fc1.w"] + params[f"{p}.ffn.fc1.b"]
                h = jax.nn.gelu(h, approximate=False)
                ffn = h @ params[f"{p}.ffn.fc2.w"] + \
                    params[f"{p}.ffn.fc2.b"]
                x = _layer_norm(x + ffn, params[f"{p}.ln2.w"],
                                params[f"{p}.ln2.b"])

        with _part("lm_head"):
            logits = x @ params["lm_out.w"] + params["lm_out.b"]
        return logits, k, v


class DecodeEngine:
    """Ties the model step to the page pool for the decode scheduler.

    Holds the donated device pools, the host page tables, and per-slot
    cursors; the scheduler drives :meth:`run_iteration` with whatever
    requests currently occupy slots.  Greedy (argmax) decoding — the
    serving contract this PR needs; sampling strategies are a follow-on.
    """

    def __init__(self, cfg, params_or_scope, max_slots: int = 4,
                 page_len: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 max_seq: Optional[int] = None,
                 program=None):
        from ..flags import get_flags
        fl = get_flags(["FLAGS_serving_kv_page_len",
                        "FLAGS_serving_kv_pages"])
        if program is not None:
            # static GSPMD-serving gate (analysis.sharding): the paged
            # pools below host full per-head pages and full unsharded
            # params on ONE chip, so a model-parallel-sharded decode
            # program is refused HERE, naming its offending specs,
            # instead of producing silently-wrong gathers at step time
            from ..analysis.sharding import check_decode_hostable
            check_decode_hostable(program)
        self.cfg = cfg
        self.page_len = int(page_len or fl["FLAGS_serving_kv_page_len"])
        self.max_seq = int(max_seq or cfg.max_pos)
        self.max_pages = -(-self.max_seq // self.page_len)  # ceil div
        self.max_slots = int(max_slots)
        n_pages = int(n_pages or fl["FLAGS_serving_kv_pages"]) or \
            (1 + self.max_slots * self.max_pages)
        if hasattr(params_or_scope, "find_var"):
            self.params = params_from_scope(params_or_scope, cfg)
        else:
            self.params = {n: jnp.asarray(a)
                           for n, a in dict(params_or_scope).items()}
        self.model = GPTDecodeModel(cfg, self.page_len, self.max_pages)
        self.cache = PagedKVCache(
            cfg.n_layer, n_pages, self.page_len, cfg.n_head,
            cfg.d_model // cfg.n_head, self.max_slots)
        # host-side page table mirror fed to every step
        self.page_table = np.zeros((self.max_slots, self.max_pages),
                                   np.int32)

    @property
    def trace_count(self) -> int:
        return self.model.trace_count

    def reserve_slot(self, slot: int, n_pages: int) -> bool:
        """Allocate a request's WORST-CASE page count up front (rolled
        back on shortfall).  Admission-time reservation is what makes
        the decode loop deadlock-free: two optimistically-admitted
        requests could otherwise each stall on the other's unreleased
        pages mid-growth — and completions happen on the same thread
        that would be stalling, so nothing would ever free them."""
        if n_pages > self.max_pages:
            return False
        got = []
        for _ in range(n_pages):
            p = self.cache.alloc_page(slot)
            if p is None:
                self.cache.free_slot(slot)   # roll back the partial grab
                self.page_table[slot, :] = 0
                return False
            got.append(p)
        for i, p in enumerate(got):
            self.page_table[slot, i] = p
        return True

    def ensure_page(self, slot: int, pos: int) -> bool:
        """Make sure the page covering ``pos`` exists for ``slot``;
        False when the pool is exhausted (caller defers the request)."""
        need = pos // self.page_len
        if need >= self.max_pages:
            return False         # past the engine's max context window
        owned = len(self.cache.pages_of(slot))
        while owned <= need:
            page = self.cache.alloc_page(slot)
            if page is None:
                return False
            self.page_table[slot, owned] = page
            owned += 1
        return True

    def release_slot(self, slot: int) -> None:
        self.cache.free_slot(slot)
        self.page_table[slot, :] = 0

    #: ``perf_counter`` marks of the last :meth:`run_iteration`: entered,
    #: the jitted step returned (dispatched), the logits ready on the
    #: device, the logits on the host.  The scheduler turns them into the
    #: ``serving.decode_step.*`` child spans of its iteration span.
    phase_marks: Optional[tuple] = None

    def run_iteration(self, ids, pos, active):
        """One decode step over all slots; returns logits [S, vocab]
        (host numpy) after updating the donated pools."""
        t0 = time.perf_counter()
        logits, self.cache.k, self.cache.v = self.model.step(
            self.params, self.cache.k, self.cache.v, ids, pos,
            self.page_table, active)
        t1 = time.perf_counter()
        logits.block_until_ready()   # np.asarray would wait here anyway
        t2 = time.perf_counter()
        out = np.asarray(logits)
        self.phase_marks = (t0, t1, t2, time.perf_counter())
        return out
