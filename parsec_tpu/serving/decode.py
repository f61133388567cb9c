"""Continuous-batching transformer decode over DTD insertions.

The workload the north star implies (ROADMAP item 4, Orca-style
iteration-level scheduling): each live request advances one decode step
per iteration; steps are DTD task insertions whose INOUT chain on the
request's state tile serializes its own steps while steps of DIFFERENT
requests (and different tenants' pools) interleave freely under the
weighted-fair scheduler — the runtime's dataflow tracking IS the
continuous batcher.

- **KV cache as a tiled collection**: per (request, tile-index) tiles of
  ``(2, kv_tile, D)`` packed keys+values in a
  :class:`KVCacheCollection`; device-resident tiles are registered with
  the context's HBM budget manager (``device.hbm_budget_mb``) with
  next-use hints, so under memory pressure the plan-informed (Belady)
  ranking evicts the coldest cache tiles and a finished request's tiles
  are dropped outright.
- **Decode steps as DTD insertions**: step *t* reads the full prior
  cache (INPUT tiles), appends its (k, v) into the tail tile (INOUT)
  and rewrites the state vector (INOUT); the shared step kernel
  (:func:`_step_kernel`) is also what the bitwise reference replays, so
  "bitwise-correct under faults" is checked against the exact float32
  op sequence, not a tolerance.
- **Long contexts**: prompt prefill builds the whole prompt's KV cache
  and first state with ONE compiled attention call —
  :func:`~parsec_tpu.compiled.ring_attention.ring_attention` over a
  mesh when one is given (sequence-sharded ppermute ring), the dense
  jnp fold otherwise.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from ..data.collection import LocalCollection
from ..dsl import dtd


class PoisonBody(ValueError):
    """Deliberate task-body failure injected by a misbehaving tenant
    (the serving tests' poison traffic)."""


@dataclass
class DecodeConfig:
    d_model: int = 32
    n_heads: int = 2
    kv_tile: int = 8          # (k, v) pairs per cache tile
    seed: int = 7

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads


class DecodeModel:
    """Deterministic float32 decode-step weights."""

    def __init__(self, cfg: DecodeConfig):
        rng = np.random.default_rng(cfg.seed)
        D = cfg.d_model

        def w(shape):
            return (rng.standard_normal(shape) * 0.25 /
                    math.sqrt(shape[0])).astype(np.float32)

        self.cfg = cfg
        self.Wq, self.Wk, self.Wv, self.Wo = (w((D, D)) for _ in range(4))
        self.W1 = w((D, 2 * D))
        self.W2 = w((2 * D, D))

    def init_state(self, rid: int) -> np.ndarray:
        rng = np.random.default_rng(self.cfg.seed * 1_000_003 + rid)
        return rng.standard_normal(self.cfg.d_model).astype(np.float32)


def _ffn_tail(x: np.ndarray, ctx_vec: np.ndarray,
              model: DecodeModel) -> np.ndarray:
    """Output projection + residual FFN + tanh over one position's
    attention context — shared by the stepwise decode kernel and the
    compiled prompt prefill so both land on the same float32 tail."""
    o = ctx_vec @ model.Wo
    h1 = x + o
    h2 = h1 + np.maximum(h1 @ model.W1, np.float32(0.0)) @ model.W2
    return np.tanh(h2)


def _attend(x: np.ndarray, K: np.ndarray, V: np.ndarray,
            model: DecodeModel) -> np.ndarray:
    """One decode attention + FFN step over the cached (K, V) rows —
    float32 throughout, fixed op order (the bitwise contract both the
    task body and the reference replay)."""
    cfg = model.cfg
    H, dh = cfg.n_heads, cfg.d_head
    q = (x @ model.Wq).reshape(H, dh)
    Kh = K.reshape(K.shape[0], H, dh)
    Vh = V.reshape(V.shape[0], H, dh)
    ctx = np.empty((H, dh), dtype=np.float32)
    scale = np.float32(1.0 / math.sqrt(dh))
    for h in range(H):
        s = (Kh[:, h, :] @ q[h]) * scale
        m = np.float32(s.max())
        e = np.exp(s - m, dtype=np.float32)
        w = e / np.float32(e.sum())
        ctx[h] = w @ Vh[:, h, :]
    return _ffn_tail(x, ctx.reshape(H * dh), model)


def _step_kernel(x: np.ndarray, prevs: List[np.ndarray],
                 tail: np.ndarray, slot: int, model: DecodeModel):
    """Shared decode-step kernel: append (k, v) of ``x`` at ``slot`` of
    the tail tile, attend over the full cache, return (new state, new
    tail). Functional: the tail is copied, never mutated in place
    (snapshot readers of the prior version stay valid — the DTD
    functional-body contract)."""
    k = x @ model.Wk
    v = x @ model.Wv
    tail = tail.copy()
    tail[0, slot] = k
    tail[1, slot] = v
    if prevs:
        K = np.concatenate([p[0] for p in prevs] + [tail[0, :slot + 1]],
                           axis=0)
        V = np.concatenate([p[1] for p in prevs] + [tail[1, :slot + 1]],
                           axis=0)
    else:
        K = tail[0, :slot + 1]
        V = tail[1, :slot + 1]
    return _attend(x, K, V, model), tail


def _decode_body(state, tail, *rest):
    """DTD task body of one decode step. ``rest`` = the request's prior
    (full) KV tiles, then the per-step meta dict (ValueArg)."""
    prevs, meta = list(rest[:-1]), rest[-1]
    t = meta["t"]
    if meta.get("poison_at") is not None and t == meta["poison_at"]:
        raise PoisonBody(
            f"poison body: request {meta['req']} step {t}")
    return _step_kernel(state, prevs, tail, meta["slot"], meta["model"])


def _done_body(state, meta):
    """Completion sentinel: an INPUT-only reader of the request's state
    tile, RAW-chained behind the final decode step — so it runs
    strictly AFTER the runtime wrote the final step's outputs back to
    the collections. Recording completion from the final step's own
    body would fire BEFORE its write-back, racing any cleanup."""
    done = meta.get("on_done")
    if done is not None:
        done(meta["req"], state)


def _prompt_of(model: DecodeModel, rid: int, prompt_len: int) -> np.ndarray:
    rng = np.random.default_rng(model.cfg.seed * 7_919 + rid)
    return rng.standard_normal(
        (prompt_len, model.cfg.d_model)).astype(np.float32)


def _prefill_request(model: DecodeModel, rid: int, prompt_len: int,
                     mesh=None):
    """Prompt prefill for one request: K/V of every prompt position
    (packed into whole leading KV tiles by the caller) and the initial
    decode state — the LAST position's attention context from ONE
    compiled attention call (:func:`prefill_attention`: ring over a
    mesh, dense otherwise) folded through the shared FFN tail. Returns
    ``(x0, K, V)`` as float32 numpy; deterministic per (model, rid,
    backend), so the reference replay reproduces it bitwise."""
    cfg = model.cfg
    if prompt_len % cfg.kv_tile:
        raise ValueError(
            f"prompt_len {prompt_len} must be a multiple of kv_tile "
            f"{cfg.kv_tile} (whole prefilled cache tiles)")
    prompt = _prompt_of(model, rid, prompt_len)
    K = prompt @ model.Wk
    V = prompt @ model.Wv
    ctx_rows = prefill_attention(model, prompt, mesh=mesh, causal=True)
    x0 = _ffn_tail(prompt[-1], ctx_rows[-1], model)
    return x0, K, V


def _packed_tiles(model: DecodeModel, K: np.ndarray,
                  V: np.ndarray) -> List[np.ndarray]:
    cfg = model.cfg
    kt = cfg.kv_tile
    tiles = []
    for j in range(K.shape[0] // kt):
        tile = np.zeros((2, kt, cfg.d_model), dtype=np.float32)
        tile[0] = K[j * kt:(j + 1) * kt]
        tile[1] = V[j * kt:(j + 1) * kt]
        tiles.append(tile)
    return tiles


def reference_decode(model: DecodeModel, rid: int, n_steps: int,
                     prompt_len: int = 0, mesh=None) -> np.ndarray:
    """Single-threaded replay of ``n_steps`` decode steps for request
    ``rid`` (after an optional prompt prefill) through the SAME kernels
    the engine runs — the bitwise oracle."""
    cfg = model.cfg
    if prompt_len:
        x, K, V = _prefill_request(model, rid, prompt_len, mesh=mesh)
        tiles = _packed_tiles(model, K, V)
    else:
        x = model.init_state(rid)
        tiles: List[np.ndarray] = []
    for t in range(prompt_len, prompt_len + n_steps):
        j, slot = divmod(t, cfg.kv_tile)
        if slot == 0:
            tiles.append(np.zeros((2, cfg.kv_tile, cfg.d_model),
                                  dtype=np.float32))
        x, tiles[j] = _step_kernel(x, tiles[:j], tiles[j], slot, model)
    return x


# ------------------------------------------------------- paged (ISSUE 15)
def token_embedding(model: DecodeModel, tok: int) -> np.ndarray:
    """Deterministic float32 embedding of one token id (cached on the
    model). Token-identified prompts are what make prefixes SHAREABLE:
    two requests presenting the same token ids mean the same bytes."""
    cache = getattr(model, "_emb_cache", None)
    if cache is None:
        cache = model._emb_cache = {}
    e = cache.get(tok)
    if e is None:
        rng = np.random.default_rng(model.cfg.seed * 524_287 + int(tok))
        e = rng.standard_normal(model.cfg.d_model).astype(np.float32)
        e.setflags(write=False)
        cache[tok] = e
    return e


def page_rows(model: DecodeModel, toks) -> np.ndarray:
    """(k, v) rows for ``toks`` as ``(2, len(toks), D)`` — computed
    per-ROW (vector @ matrix), so a row's bytes depend ONLY on its own
    token: prefill chunking, partial-page fills, and prefix sharing can
    never change results bitwise (a row reused from the cache is
    byte-identical to the row the no-sharing replay computes)."""
    out = np.empty((2, len(toks), model.cfg.d_model), dtype=np.float32)
    for i, tok in enumerate(toks):
        e = token_embedding(model, tok)
        out[0, i] = e @ model.Wk
        out[1, i] = e @ model.Wv
    return out


def paged_prefill_state(model: DecodeModel, tokens, pages) -> np.ndarray:
    """Initial decode state after a token prompt: the LAST position's
    attention over every prompt row (assembled from the page run) folded
    through the shared FFN tail — the exact numpy kernel
    :func:`reference_decode_paged` replays, so sharing stays bitwise-
    invisible. ``pages`` must cover ``len(tokens)`` rows."""
    S = len(tokens)
    K = np.concatenate([p[0] for p in pages], axis=0)[:S]
    V = np.concatenate([p[1] for p in pages], axis=0)[:S]
    return _attend(token_embedding(model, tokens[-1]), K, V, model)


def reference_decode_paged(model: DecodeModel, tokens, n_steps: int,
                           page_tokens: int) -> np.ndarray:
    """Single-threaded no-sharing replay of a token-prompted paged
    request through the SAME kernels the engine runs (per-row prefill,
    last-position attention, per-step :func:`_step_kernel`) — the
    bitwise oracle proving prefix sharing, chunked prefill, and
    speculative decode are invisible to results."""
    pt = page_tokens
    tokens = tuple(tokens)
    S = len(tokens)
    if S < 1:
        raise ValueError("paged decode requires a non-empty prompt")
    n_pages = (S + n_steps + pt - 1) // pt
    pages = [np.zeros((2, pt, model.cfg.d_model), dtype=np.float32)
             for _ in range(n_pages)]
    for j in range((S + pt - 1) // pt):
        toks = tokens[j * pt:min((j + 1) * pt, S)]
        rows = page_rows(model, toks)
        pages[j][:, :len(toks)] = rows
    x = paged_prefill_state(model, tokens,
                            pages[:(S + pt - 1) // pt])
    for t in range(S, S + n_steps):
        j, slot = divmod(t, pt)
        x, pages[j] = _step_kernel(x, pages[:j], pages[j], slot, model)
    return x


def _paged_body(*vals):
    """Single DTD body for every row of a paged request's task graph —
    ONE ``insert_tasks`` batch per request means ONE admission check:
    the graph is admitted all-or-nothing (a mid-graph rejection cannot
    leave a half-inserted request leaking pages). The trailing ValueArg
    meta dict selects the role:

    - ``prefill``: fill this chunk's pages' (k, v) rows (INOUT pages;
      functional — copies, never mutates, so snapshot readers stay
      valid). Rides the wfq prefill lane (priority < 0).
    - ``state``: last-position attention over the prompt pages (INPUT)
      into the request's state tile (INOUT); publishes the full prompt
      pages to the radix tree — the pages are final HERE (this task is
      RAW-ordered behind every chunk's write-back), which is what makes
      cross-pool sharing race-free.
    - ``step``: one decode step (exactly :func:`_decode_body`).
    - ``verify``: one speculative-decode window (serving/spec.py).
    - ``done``: the completion sentinel (:func:`_done_body`).
    """
    meta = vals[-1]
    kind = meta["kind"]
    if kind == "step":
        # the page TABLE is the argument, not the pages (the
        # PagedAttention shape): prior pages are read by pid at
        # EXECUTION time. Correct without per-page dataflow edges
        # because (a) the request's INOUT state chain serializes its
        # steps, (b) the state task INPUT-fences every prefill write,
        # (c) write-backs precede successor release, and (d) the
        # request's page refcounts keep every pid immutable-in-place
        # until release — so the 40+ INPUT TileArgs a long-context
        # step would otherwise carry (and their insert/dep-count cost)
        # collapse into one tuple of ints.
        t = meta["t"]
        if meta.get("poison_at") is not None and t == meta["poison_at"]:
            raise PoisonBody(
                f"poison body: request {meta['req']} step {t}")
        dc_read = meta["dc_read"]
        prevs = [dc_read((pid,)) for pid in meta["prev_pids"]]
        return _step_kernel(vals[0], prevs, vals[1], meta["slot"],
                            meta["model"])
    if kind == "steps":
        # multi-step decode window (serving.kv_decode_window > 1): the
        # EXACT per-step kernel sequence run W steps per task — same
        # floats, W× fewer scheduler passes per request
        n_rw = meta["n_rw"]
        x = vals[0]
        rw = [v.copy() for v in vals[1:1 + n_rw]]
        dc_read = meta["dc_read"]
        pages = [dc_read((pid,)) for pid in meta["prev_pids"]] + rw
        pt, model = meta["pt"], meta["model"]
        j_base = len(pages) - n_rw
        for i in range(meta["steps"]):
            t = meta["t0"] + i
            if meta.get("poison_at") is not None and \
                    t == meta["poison_at"]:
                raise PoisonBody(
                    f"poison body: request {meta['req']} step {t}")
            j, slot = divmod(t, pt)
            x, new_tail = _step_kernel(x, pages[:j], pages[j], slot,
                                       model)
            pages[j] = new_tail
            rw[j - j_base] = new_tail
        return (x, *rw)
    if kind == "done":
        return _done_body(vals[0], meta)
    model = meta["model"]
    if kind == "prefill":
        pages = vals[:-1]
        out = []
        for page, toks in zip(pages, meta["toks"]):
            page = page.copy()
            page[:, :len(toks)] = page_rows(model, toks)
            out.append(page)
        return out[0] if len(out) == 1 else tuple(out)
    if kind == "state":
        # cached-prefix pages are final and refcount-held, so they are
        # read by pid (no dataflow edge); only the request's OWN
        # suffix-prefill pages arrive as INPUT flows — the fence that
        # orders this task behind its chunk tasks' write-backs
        dc_read = meta["dc_read"]
        pages = [dc_read((pid,)) for pid in meta["prev_pids"]]
        pages += list(vals[1:-1])
        x0 = paged_prefill_state(model, meta["tokens"], pages)
        publish = meta.get("publish")
        if publish is not None:
            publish()
        return x0
    if kind == "verify":
        from .spec import verify_exec
        return verify_exec(vals, meta)
    raise ValueError(f"unknown paged row kind {kind!r}")


# --------------------------------------------------------------- prefill
def prefill_attention(model: DecodeModel, prompt: np.ndarray,
                      mesh=None, causal: bool = True) -> np.ndarray:
    """Long-context prompt prefill: one compiled attention call over the
    whole prompt ``(S, D)`` — ring attention (sequence-sharded ppermute
    ring, ``compiled/ring_attention.py``) when a mesh is given, the
    dense jnp fold otherwise. Returns the attention output ``(S, D)``
    as float32 numpy."""
    import jax.numpy as jnp
    from ..compiled.ring_attention import dense_attention, ring_attention
    cfg = model.cfg
    S = prompt.shape[0]
    H, dh = cfg.n_heads, cfg.d_head
    Q = (prompt @ model.Wq).reshape(S, H, dh)
    K = (prompt @ model.Wk).reshape(S, H, dh)
    V = (prompt @ model.Wv).reshape(S, H, dh)
    if mesh is not None:
        out = ring_attention(jnp.asarray(Q), jnp.asarray(K),
                             jnp.asarray(V), mesh, causal=causal)
    else:
        out = dense_attention(jnp.asarray(Q), jnp.asarray(K),
                              jnp.asarray(V), causal=causal)
    return np.asarray(out, dtype=np.float32).reshape(S, H * dh)


# ------------------------------------------------------------ collections
class KVCacheCollection(LocalCollection):
    """Dict-backed KV cache whose device-resident tiles are registered
    with the HBM budget manager: every write refreshes the tile's
    next-use hint (a live request touches its whole cache again next
    step), so the Belady ranking evicts the longest-idle cache tiles
    first and :meth:`drop_request` releases a finished request's tiles
    outright. Host (numpy) tiles pass through untracked."""

    def __init__(self, name: str, hbm=None, myrank: int = 0):
        super().__init__(name, myrank=myrank)
        self.hbm = hbm
        self._clock = 0

    def _mkey(self, key):
        return (id(self), tuple(key))

    def write_tile(self, key, value) -> None:
        super().write_tile(key, value)
        hbm = self.hbm
        if hbm is None or not isinstance(value, hbm.jax.Array):
            return
        self._clock += 1

        def _spill(_k, host, dc=self, key=key):
            LocalCollection.write_tile(dc, key, host)

        try:
            hbm.put(self._mkey(key), value, next_use=self._clock + 1,
                    spill=_spill)
        except MemoryError:
            pass                      # tile bigger than the whole budget

    def drop_request(self, rid: int) -> None:
        """Release a finished request's cache: HBM-manager entries AND
        the host tiles (a persistent serving engine would otherwise
        grow by one request's KV forever)."""
        for key in self.keys():
            if key[0] == rid:
                if self.hbm is not None:
                    self.hbm.drop(self._mkey(key))
                self.drop_tile(key)


# ---------------------------------------------------------------- engine
@dataclass
class PendingRequest:
    rid: int
    n_steps: int
    submitted_t: float
    prompt_len: int = 0
    mesh: object = None
    done_evt: threading.Event = field(default_factory=threading.Event)
    finished_t: Optional[float] = None
    result: Optional[np.ndarray] = None
    # paged (KV state layer) requests — ISSUE 15
    tokens: Optional[tuple] = None      # token prompt (None = classic)
    pages: Optional[list] = None        # page table: ordered pids
    match: object = None                # radix MatchHandle (node pins)
    n_cached: int = 0                   # prefix tokens served from cache
    spec: object = None                 # speculative-decode controller

    def latency_s(self) -> Optional[float]:
        return (self.finished_t - self.submitted_t
                if self.finished_t is not None else None)


class DecodeEngine:
    """Continuous-batching decode front end for ONE tenant.

    ``start()`` submits a persistent DTD pool through the serving
    runtime; ``request()`` inserts a request's decode steps (admission
    control applies per insert — :class:`~.runtime.AdmissionRejected`
    propagates to the caller); completion is detected per request by
    the final step's body callback, so per-request latency is
    end-to-end through the runtime, not a wrapper around wait()."""

    def __init__(self, ctx, name: str, cfg: Optional[DecodeConfig] = None,
                 tenant=None, model: Optional[DecodeModel] = None,
                 kv_layer=None, **submit_kwargs):
        self.ctx = ctx
        self.name = name
        self.cfg = cfg or DecodeConfig()
        self.model = model or DecodeModel(self.cfg)
        self.tenant = tenant
        # KV state layer (serving/kv.py): when attached, token-prompted
        # requests take the paged path — radix prefix match, paged
        # allocation, chunked prefill on the wfq prefill lane, optional
        # speculative decode
        self.kv_layer = kv_layer
        self.submit_kwargs = submit_kwargs
        # collections OWNED by this context's rank: a decode engine on
        # a worker rank of an elastic mesh must place its steps locally
        # (rank_of = 0 would ship every task to the front-end rank)
        self.state = LocalCollection(f"{name}_state",
                                     myrank=ctx.my_rank)
        self.kv = KVCacheCollection(f"{name}_kv", hbm=ctx.hbm,
                                    myrank=ctx.my_rank)
        self.tp = None
        self.submission = None
        self.pending: Dict[int, PendingRequest] = {}
        self._lock = threading.Lock()

    def start(self) -> "DecodeEngine":
        self.tp = dtd.Taskpool(f"{self.name}_decode")
        self.submission = self.ctx.submit(self.tp, tenant=self.tenant,
                                          **self.submit_kwargs)
        return self

    def _on_done(self, rid: int, h: np.ndarray) -> None:
        # record only — tile cleanup happens in release(): this runs
        # INSIDE the final step's body, before the runtime writes the
        # step's outputs back, so dropping tiles here would race the
        # completion write-back
        with self._lock:
            req = self.pending.get(rid)
        if req is not None:
            req.finished_t = time.monotonic()
            req.result = h
            req.done_evt.set()

    def request(self, rid: int, n_steps: int,
                poison_at: Optional[int] = None,
                prompt_len: int = 0, mesh=None,
                tokens=None) -> PendingRequest:
        """Admit one request and insert its decode steps. With
        ``prompt_len`` (a multiple of ``kv_tile``) the prompt's
        attention runs as ONE compiled prefill call (ring attention
        over ``mesh`` when given, dense otherwise) that SEEDS the
        request's KV cache tiles and initial state; the stepwise decode
        then attends over prompt + generated positions.

        With ``tokens`` (a sequence of token ids; requires a
        ``kv_layer``) the request takes the PAGED path instead: longest
        cached prefix served from the radix tree, only the suffix
        chunk-prefilled (wfq prefill lane), optional speculative decode
        (``serving.kv_spec_draft``)."""
        if tokens is not None:
            if self.kv_layer is None:
                raise ValueError(
                    "token-prompted requests need a KV state layer "
                    "(DecodeEngine(kv_layer=...))")
            return self._request_paged(rid, tuple(int(t) for t in tokens),
                                       n_steps, poison_at)
        cfg, model = self.cfg, self.model
        req = PendingRequest(rid, n_steps, time.monotonic(),
                             prompt_len=prompt_len, mesh=mesh)
        with self._lock:
            self.pending[rid] = req
        if prompt_len:
            x0, K, V = _prefill_request(model, rid, prompt_len,
                                        mesh=mesh)
            prefilled = _packed_tiles(model, K, V)
        else:
            x0, prefilled = model.init_state(rid), []
        self.state.write_tile((rid,), x0)
        for j, tile in enumerate(prefilled):
            self.kv.write_tile((rid, j), tile)
        t0 = prompt_len
        n_tiles = (t0 + n_steps + cfg.kv_tile - 1) // cfg.kv_tile
        for j in range(len(prefilled), n_tiles):
            self.kv.write_tile((rid, j), np.zeros(
                (2, cfg.kv_tile, cfg.d_model), dtype=np.float32))
        rows = []
        for t in range(t0, t0 + n_steps):
            j, slot = divmod(t, cfg.kv_tile)
            args = [dtd.TileArg(self.state, (rid,), dtd.INOUT),
                    dtd.TileArg(self.kv, (rid, j), dtd.INOUT)]
            args += [dtd.TileArg(self.kv, (rid, jj), dtd.INPUT)
                     for jj in range(j)]
            args.append(dtd.ValueArg({
                "req": rid, "t": t, "slot": slot,
                "model": model, "poison_at": poison_at}))
            rows.append(args)
        try:
            self.tp.insert_tasks(_decode_body, rows)
            # completion sentinel (see _done_body): post-write-back
            self.tp.insert_task(
                _done_body, dtd.TileArg(self.state, (rid,), dtd.INPUT),
                dtd.ValueArg({"req": rid, "on_done": self._on_done}))
        except Exception:
            # rejected insert (admission window, quarantine, aborted
            # pool): release the tiles written above too, or every
            # rejected rid of an open-loop stream leaks one state +
            # n_tiles KV tiles into the persistent collections
            with self._lock:
                self.pending.pop(rid, None)
            self.kv.drop_request(rid)
            self.state.drop_tile((rid,))
            raise
        return req

    # ------------------------------------------------ paged path (ISSUE 15)
    def _request_paged(self, rid: int, tokens: tuple, n_steps: int,
                       poison_at: Optional[int]) -> PendingRequest:
        """Token-prompted request through the KV state layer: match the
        longest cached prefix, allocate the rest of the page table,
        then insert the request's WHOLE task graph (prefill chunks on
        the wfq prefill lane, state, decode steps or speculative verify
        windows, completion sentinel) as ONE batch — one admission
        check, admitted all-or-nothing."""
        from ..utils import mca_param
        from .kv import KVPagesExhausted
        from .runtime import AdmissionRejected
        layer, model = self.kv_layer, self.model
        pt = layer.page_tokens
        S = len(tokens)
        if S < 1:
            raise ValueError("paged decode requires a non-empty prompt")
        total = S + n_steps
        n_pages = (total + pt - 1) // pt
        req = PendingRequest(rid, n_steps, time.monotonic(),
                             prompt_len=S, tokens=tokens)
        handle = layer.match(tokens)
        c_pages = len(handle.pids)
        req.match = handle
        req.n_cached = handle.n_tokens
        try:
            own = layer.pool.alloc(n_pages - c_pages)
        except KVPagesExhausted as exc:
            self._release_paged_refs(handle.pids, handle)
            raise AdmissionRejected(str(exc)) from exc
        pages = list(handle.pids) + own
        req.pages = pages
        with self._lock:
            self.pending[rid] = req
        placeholder = np.zeros(model.cfg.d_model, dtype=np.float32)
        req._spec_x0_ph = placeholder   # spec watcher: write-back is
        #                                 detected by object identity
        self.state.write_tile((rid,), placeholder)
        dc = layer.dc
        n_prompt_pages = (S + pt - 1) // pt

        rows, prios = [], []
        # chunked prefill of the UNCACHED suffix pages only
        chunk = max(1, int(mca_param.get("serving.kv_prefill_chunk", 4)))
        j = c_pages
        while j < n_prompt_pages:
            span = list(range(j, min(j + chunk, n_prompt_pages)))
            rows.append(
                [dtd.TileArg(dc, (pages[p],), dtd.INOUT) for p in span]
                + [dtd.ValueArg({
                    "kind": "prefill", "model": model, "req": rid,
                    "toks": [tokens[p * pt:min((p + 1) * pt, S)]
                             for p in span]})])
            prios.append(-1)
            j += chunk
        layer.note_prefilled(S - handle.n_tokens)
        # prefill-state task: INPUT every prompt page; publishes the
        # FULL prompt pages to the radix tree (bytes final here)
        full_prompt_pages = S // pt

        def _publish(_layer=layer, _tokens=tokens[:full_prompt_pages * pt],
                     _pids=tuple(pages[:full_prompt_pages])):
            _layer.publish(_tokens, _pids)

        rows.append(
            [dtd.TileArg(self.state, (rid,), dtd.INOUT)]
            + [dtd.TileArg(dc, (pages[p],), dtd.INPUT)
               for p in range(c_pages, n_prompt_pages)]
            + [dtd.ValueArg({"kind": "state", "model": model,
                             "req": rid, "tokens": tokens,
                             "prev_pids": tuple(pages[:c_pages]),
                             "dc_read": dc.data_of,
                             "publish": _publish})])
        prios.append(-1)
        # decode rows: plain per-step tasks, or speculative windows
        draft = int(mca_param.get("serving.kv_spec_draft", 0))
        if draft > 0 and n_steps > 0:
            from . import spec
            req.spec = spec.SpecController(self, req, draft)
            rows_v, prios_v = req.spec.verify_rows(poison_at)
            rows.extend(rows_v)
            prios.extend(prios_v)
        elif int(mca_param.get("serving.kv_decode_window", 1)) > 1:
            win = int(mca_param.get("serving.kv_decode_window", 1))
            t = S
            while t < S + n_steps:
                steps = min(win, S + n_steps - t)
                j0, j1 = t // pt, (t + steps - 1) // pt
                args = [dtd.TileArg(self.state, (rid,), dtd.INOUT)]
                args += [dtd.TileArg(dc, (pages[j],), dtd.INOUT)
                         for j in range(j0, j1 + 1)]
                args.append(dtd.ValueArg({
                    "kind": "steps", "req": rid, "t0": t,
                    "steps": steps, "pt": pt, "n_rw": j1 - j0 + 1,
                    "model": model, "poison_at": poison_at,
                    "prev_pids": tuple(pages[:j0]),
                    "dc_read": dc.data_of}))
                rows.append(args)
                prios.append(0)
                t += steps
        else:
            for t in range(S, S + n_steps):
                pj, slot = divmod(t, pt)
                rows.append([
                    dtd.TileArg(self.state, (rid,), dtd.INOUT),
                    dtd.TileArg(dc, (pages[pj],), dtd.INOUT),
                    dtd.ValueArg({
                        "kind": "step", "req": rid, "t": t,
                        "slot": slot, "model": model,
                        "poison_at": poison_at,
                        "prev_pids": tuple(pages[:pj]),
                        "dc_read": dc.data_of})])
                prios.append(0)
        rows.append([dtd.TileArg(self.state, (rid,), dtd.INPUT),
                     dtd.ValueArg({"kind": "done", "req": rid,
                                   "on_done": self._on_done})])
        prios.append(0)
        try:
            self.tp.insert_tasks(_paged_body, rows, priorities=prios)
        except Exception:
            if self.tp.error is None and not self.tp.cancelled:
                # rejected by admission: the batch's single admission
                # check ran BEFORE any row was inserted — release now
                with self._lock:
                    self.pending.pop(rid, None)
                self._release_paged(req)
            # else: the pool aborted mid-batch — some rows may be in
            # flight, so the request stays pending and drain()'s
            # dead-pool sweep releases it after the drain completes
            raise
        if req.spec is not None:
            req.spec.start_branch()
        return req

    def _release_paged_refs(self, pids, handle) -> None:
        layer = self.kv_layer
        for pid in pids:
            layer.pool.release(pid)
        if handle is not None:
            handle.unlock()

    def _release_paged(self, req: PendingRequest) -> None:
        """Release one paged request's resources: the branch pool's
        pages (speculation), every page-table reference (the last one
        frees the page, its tile, and its HBM entry), the radix node
        pins, and the state tile."""
        if req.spec is not None:
            req.spec.release()
            req.spec = None
        if req.pages is not None:
            self._release_paged_refs(req.pages, req.match)
            req.pages = None
            req.match = None
        self.state.drop_tile((req.rid,))

    def drain(self, timeout: float = 60.0,
              prune: bool = True) -> List[PendingRequest]:
        """Wait for every pending request; returns the finished ones
        (requests of an aborted/cancelled pool stay unfinished). With
        ``prune`` (default) the finished requests are released — their
        state/KV tiles and bookkeeping are reclaimed, which is what
        keeps a persistent engine's footprint bounded under an
        open-loop stream; results stay on the returned handles for
        verification.

        DEAD-POOL sweep (ISSUE 15 leak audit): when the engine's pool
        was cancelled (deadline reaper, explicit cancel) or aborted
        (poison body, quarantine), its unfinished requests can never
        finish — after the pool's in-flight tasks drain
        (``_complete_evt``; dropped-at-select tasks never touch tiles,
        in-flight ones have written back by then), their tiles, pages,
        and HBM entries are released too. Without this, every
        deadline-cancelled or quarantine-aborted request leaked its
        state tile + KV tiles/pages into the persistent collections."""
        deadline = time.monotonic() + timeout
        with self._lock:
            reqs = list(self.pending.values())
        for req in reqs:
            left = max(0.0, deadline - time.monotonic())
            req.done_evt.wait(left)
            if self.tp is not None and self.tp.error is not None:
                break
        finished = [r for r in reqs if r.done_evt.is_set()]
        if prune:
            for r in finished:
                self.release(r)
            tp = self.tp
            if tp is not None and (tp.cancelled or tp.error is not None):
                # releasing BEFORE the pool fully terminated could race
                # an in-flight task's write-back against page reuse
                tp._complete_evt.wait(max(0.0,
                                          deadline - time.monotonic()))
                if tp._complete_evt.is_set():
                    with self._lock:
                        dead = [r for r in self.pending.values()
                                if not r.done_evt.is_set()]
                    for r in dead:
                        self.release(r)
        return finished

    def release(self, req: PendingRequest) -> None:
        """Reclaim one collected request: pending-table entry, state
        tile, KV cache tiles (host + HBM-manager entries) or — paged —
        page-table references, radix pins, and the speculative branch.
        ``req.result`` survives for verification."""
        with self._lock:
            self.pending.pop(req.rid, None)
        if req.pages is not None or req.spec is not None:
            self._release_paged(req)
            return
        self.kv.drop_request(req.rid)
        self.state.drop_tile((req.rid,))

    def verify(self, req: PendingRequest) -> bool:
        """Bitwise check of a finished request against the reference
        replay (same float32 kernels — prefill included — same op
        order). Paged requests replay the NO-SHARING paged oracle, so
        prefix sharing and speculation must be invisible to pass."""
        if req.tokens is not None:
            ref = reference_decode_paged(self.model, req.tokens,
                                         req.n_steps,
                                         self.kv_layer.page_tokens)
        else:
            ref = reference_decode(self.model, req.rid, req.n_steps,
                                   prompt_len=req.prompt_len,
                                   mesh=req.mesh)
        return req.result is not None and \
            req.result.shape == ref.shape and \
            bool(np.all(req.result == ref))

    def close(self) -> None:
        """Drain and retire the engine's pool (aborted pools count as
        already drained), then release every remaining request — a
        closed engine holds no tiles, pages, or HBM entries."""
        tp = self.tp
        if tp is not None and not tp.completed:
            try:
                tp.wait()
            except RuntimeError:
                pass                  # aborted/cancelled pools: done
        with self._lock:
            left = list(self.pending.values())
        for req in left:
            self.release(req)
