"""Latent (MLA) KV compression suite.

The latent cache stores ONE fused ``[rank + rope_head_dim]`` record per
token instead of per-head K/V planes — a different model family
(``mla``), not a lossy re-encoding of a value cache. These tests pin the
contracts the rest of the stack leans on:

* **registry gate** — ``LatentConfig`` is rejected outside the ``mla``
  family, and ``mla`` requires it enabled;
* **determinism** — same config + seed ⇒ identical tokens, greedy and
  sampled, f32 and int8 stored forms;
* **accounting** — ``kv_bytes_per_token`` reports the latent stored
  form's true footprint and attention dispatches count
  ``latent_decompress_dispatches``;
* **migration** — ``export_session`` snapshots the latent stored form
  (``c``/``cs`` planes, never per-head K/V) and the codec round-trip
  resumes BYTE-EXACT on a fresh engine;
* **spill tier** — evict → host arena → reload is bit-exact under the
  latent cache (the arena is layout-agnostic: it round-trips whatever
  plane dict ``read_page`` hands it);
* **disagg** — ``prefill_export`` → ``encode_kv`` (header declares
  ``layout: "latent"``) → ``admit_prefilled`` on a latent decode engine
  matches the colocated stream; cross-family plane dicts are rejected
  on import;
* **wire schema** — decoders reject stale codec versions and unknown
  layouts with :class:`SchemaError`, which workers surface as a
  ``schema`` error reply (upgrade, not retry);
* **spec A/B normalization** — ``_spec_adapt`` folds windows as
  tokens/s PER ACTIVE SPECULATIVE ROW, so occupancy changes between
  windows cannot latch the wrong mode.
"""

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_inference_tpu.config import (
    CacheConfig,
    EngineConfig,
    LatentConfig,
    ModelConfig,
    PrefixConfig,
)
from distributed_llm_inference_tpu.disagg.kv_codec import (
    SchemaError,
    _pack,
    _unpack,
    decode_kv,
    decode_session,
    encode_error,
    encode_kv,
    encode_session,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.models.registry import validate_config

pytestmark = pytest.mark.latent

MLA_CFG = ModelConfig(
    vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
    num_heads=4, num_kv_heads=1, head_dim=16, family="mla",
    latent=LatentConfig(rank=16, rope_head_dim=8),
)
LAT_DIM = MLA_CFG.latent.lat_dim  # 24
BASE_CFG = ModelConfig(
    vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=16,
)

_PARAMS = {}


def _params(cfg):
    key = cfg.family
    if key not in _PARAMS:
        _PARAMS[key] = llama.init_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.float32
        )
    return _PARAMS[key]


PS = 8


def make_engine(cfg=MLA_CFG, kv_quant=None, num_pages=64, prefix=False,
                spill=0, batch=2, seed=1, **ekw):
    return InferenceEngine(
        cfg, _params(cfg),
        EngineConfig(max_batch_size=batch, prefill_buckets=(8, 16, 32),
                     max_seq_len=128, dtype="float32", **ekw),
        CacheConfig(kind="paged", kv_quant=kv_quant, page_size=PS,
                    num_pages=num_pages, max_pages_per_session=16,
                    prefix_caching=prefix),
        rng=jax.random.PRNGKey(seed),
        prefix_cfg=(
            PrefixConfig(prefix_share=True, spill_bytes_max=spill)
            if prefix else None
        ),
    )


def drain(engine, gid, budget=200):
    toks = []
    for _ in range(budget):
        for g, tok, fin in engine.step():
            if g != gid:
                continue
            if tok >= 0:
                toks.append(tok)
            if fin:
                return toks
    raise AssertionError("generation did not finish in budget")


def run_partway(engine, gid, min_tokens):
    got = []
    for _ in range(200):
        if len(got) >= min_tokens:
            return got
        for g, tok, fin in engine.step():
            if g != gid:
                continue
            if tok >= 0:
                got.append(tok)
            assert not fin, "session finished before the export point"
    raise AssertionError("engine stalled before the export point")


QUANTS = [None, "int8"]


# -- registry gate ------------------------------------------------------------


def test_registry_gates_latent_config():
    validate_config(MLA_CFG)  # the blessed combination
    import dataclasses as dc

    with pytest.raises(ValueError, match="latent"):
        validate_config(dc.replace(BASE_CFG, latent=MLA_CFG.latent))
    with pytest.raises(ValueError, match="latent"):
        validate_config(dc.replace(MLA_CFG, latent=None))


def test_latent_requires_paged_cache():
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(
            MLA_CFG, _params(MLA_CFG),
            EngineConfig(max_batch_size=2, prefill_buckets=(8,),
                         max_seq_len=64, dtype="float32"),
            CacheConfig(kind="dense"),
        )


# -- determinism + accounting -------------------------------------------------


@pytest.mark.parametrize("kv_quant", QUANTS)
@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_latent_decode_deterministic(kv_quant, temp):
    """Same config + seed ⇒ identical tokens (greedy AND sampled): the
    latent path consumes RNG keys exactly like the baseline engine."""
    prompt = [3, 5, 7, 11, 13]
    opts = SamplingOptions(temperature=temp, top_k=20 if temp else 0,
                           max_new_tokens=12)
    a = make_engine(kv_quant=kv_quant).generate([prompt], opts)[0]
    b = make_engine(kv_quant=kv_quant).generate([prompt], opts)[0]
    assert a == b and len(a) == 12


@pytest.mark.parametrize("kv_quant,bpt", [
    (None, 2 * LAT_DIM * 4),          # L * lat_dim * f32
    ("int8", 2 * (LAT_DIM + 4)),      # L * (int8 latent + f32 scale)
])
def test_latent_kv_bytes_per_token_gauge(kv_quant, bpt):
    eng = make_engine(kv_quant=kv_quant)
    assert eng.metrics.get_gauge("kv_bytes_per_token") == bpt
    # Baseline at the same geometry for scale: K+V * Hkv * D * 4 per layer.
    base = make_engine(BASE_CFG)
    assert base.metrics.get_gauge("kv_bytes_per_token") == 2 * 2 * 2 * 16 * 4
    eng.generate([[3, 5, 7]], SamplingOptions(max_new_tokens=4))
    assert eng.metrics.get_counter("latent_decompress_dispatches") > 0
    assert base.metrics.get_counter("latent_decompress_dispatches") == 0


# -- ragged kernel path + chunked admission -----------------------------------


@pytest.mark.parametrize("kv_quant", QUANTS)
def test_latent_ragged_parity(kv_quant):
    """The ragged mixed-phase kernel path reads the latent stored form
    through the same page-table walk (K = V = latent): byte-exact vs the
    non-ragged latent fallback."""
    ps = [[3, 5, 7], [11, 13, 17, 19, 23], [2, 4, 6, 8]]
    opts = SamplingOptions(max_new_tokens=5)
    base = make_engine(kv_quant=kv_quant, batch=4,
                       ragged_attention=False).generate(ps, opts)
    rag = make_engine(kv_quant=kv_quant, batch=4,
                      ragged_attention=True).generate(ps, opts)
    assert base == rag


def test_latent_chunked_admission_parity():
    """A long greedy prompt chunk-admitted beside live latent decode rows
    still produces the non-chunked stream."""
    import numpy as np

    rng = np.random.default_rng(7)
    mix = [[3, 5, 7], rng.integers(0, 128, size=30).tolist(), [2, 4, 6]]
    opts = SamplingOptions(max_new_tokens=6)
    base = make_engine(batch=4, ragged_attention=False).generate(mix, opts)
    eng = make_engine(batch=4, ragged_attention=True,
                      prefill_chunk_tokens=8, chunk_decode_share=0.5)
    assert eng.generate(mix, opts) == base
    assert eng.metrics.get_counter("attn_chunked_rows") > 0


# -- migration: latent stored form through the codec --------------------------


@pytest.mark.parametrize("kv_quant,temp", [
    (None, 0.0), (None, 0.8), ("int8", 0.0), ("int8", 0.8),
])
def test_latent_export_resume_byte_exact(kv_quant, temp):
    """Checkpoint mid-decode, ship through ``encode_session``, resume on
    a FRESH latent engine: continuation equals the uninterrupted stream
    bit for bit, and the snapshot carries the latent STORED form (one
    fused ``[lat_dim]`` record per token, never per-head K/V)."""
    prompt = [3, 5, 7, 11, 13]
    opts = SamplingOptions(temperature=temp, top_k=20 if temp else 0,
                           max_new_tokens=24)
    ref = make_engine(kv_quant=kv_quant)
    base = drain(ref, ref.submit(list(prompt), opts))

    victim = make_engine(kv_quant=kv_quant)
    gid = victim.submit(list(prompt), opts)
    run_partway(victim, gid, 6)
    snap = victim.export_session(gid)
    assert snap is not None
    want = {"c", "cs"} if kv_quant else {"c"}
    assert set(snap["planes"]) == want
    assert snap["planes"]["c"].shape[-1] == LAT_DIM

    frames = encode_session("mig", snap, page_size=PS)
    snap2, meta = decode_session(frames)
    assert meta["layout"] == "latent"

    dst = make_engine(kv_quant=kv_quant)
    gid2 = dst.resume_session(snap2)
    assert snap["generated"] + drain(dst, gid2) == base


# -- spill tier ---------------------------------------------------------------


@pytest.mark.parametrize("kv_quant", QUANTS)
def test_latent_spill_reload_round_trip(kv_quant):
    """Pressure-evict latent prefix pages to the host arena and reload
    them: streams stay byte-exact vs an unshared latent engine (the
    arena round-trips the latent plane dict bit for bit)."""
    opts = SamplingOptions(max_new_tokens=4, eos_token_id=-1)
    pA, pB = list(range(1, 18)), list(range(50, 74))
    e = make_engine(kv_quant=kv_quant, prefix=True, spill=1 << 20,
                    num_pages=6)  # 5 usable pages: B evicts A
    rA = e.generate([pA], opts)[0]
    rB = e.generate([pB], opts)[0]
    snap = e.metrics.snapshot()
    assert snap.get("prefix_spilled_pages", 0) >= 1
    rA2 = e.generate([pA], opts)[0]
    snap = e.metrics.snapshot()
    assert snap.get("prefix_spill_reloads", 0) >= 1
    assert snap.get("prefix_reload_errors", 0) == 0
    s = make_engine(kv_quant=kv_quant, num_pages=32)
    assert [rA, rB, rA2] == [
        s.generate([p], opts)[0] for p in (pA, pB, pA)
    ]


# -- disaggregated admission --------------------------------------------------


@pytest.mark.parametrize("kv_quant", QUANTS)
def test_latent_disagg_admit_byte_exact(kv_quant):
    """prefill_export on a latent engine → codec (header declares the
    latent layout) → admit_prefilled on a fresh latent engine: the
    decoded stream equals the colocated run token for token."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5]
    opts = SamplingOptions(max_new_tokens=6)
    base = make_engine(kv_quant=kv_quant).generate([prompt], opts)[0]
    src = make_engine(kv_quant=kv_quant)
    dst = make_engine(kv_quant=kv_quant)
    planes, first, chain = src.prefill_export(list(prompt), opts)
    frames = encode_kv("ship", planes, len(prompt), first, chain,
                       page_size=PS, quant="cs" in planes,
                       max_frame_bytes=2048)
    dec, meta = decode_kv(frames)
    assert meta["layout"] == "latent"
    assert meta["quant"] is bool(kv_quant)
    gid = dst.admit_prefilled(list(prompt), dec, meta["first_token"],
                              options=opts)
    assert drain(dst, gid) == base


def test_cross_family_planes_rejected():
    """A latent engine must refuse per-head K/V planes and vice versa —
    silently ingesting the wrong stored form would corrupt decode."""
    prompt = [1, 2, 3, 4, 5]
    opts = SamplingOptions(max_new_tokens=4)
    kv_planes, kv_first, _ = make_engine(BASE_CFG).prefill_export(
        list(prompt), opts)
    lat_planes, lat_first, _ = make_engine().prefill_export(
        list(prompt), opts)
    with pytest.raises(ValueError, match="cache family"):
        make_engine().admit_prefilled(list(prompt), kv_planes, kv_first,
                                      options=opts)
    with pytest.raises(ValueError, match="cache family"):
        make_engine(BASE_CFG).admit_prefilled(list(prompt), lat_planes,
                                              lat_first, options=opts)


# -- wire schema versioning ---------------------------------------------------


def _tamper(frame, **header_updates):
    header, chunk = _unpack(frame)
    header.update(header_updates)
    return _pack(header, chunk)


def test_codec_rejects_stale_version():
    """A v1 peer's frame (no layout vocabulary) must fail TYPED at decode
    — a SchemaError, never a misparse of latent planes as K/V."""
    planes, first, chain = make_engine().prefill_export(
        [1, 2, 3, 4, 5], SamplingOptions(max_new_tokens=4))
    frames = encode_kv("g", planes, 5, first, chain, page_size=PS)
    stale = [_tamper(f, v=1) for f in frames]
    with pytest.raises(SchemaError, match="version"):
        decode_kv(stale)
    # ... and an unknown layout tag fails the same way.
    alien = [_tamper(f, layout="holographic") for f in frames]
    with pytest.raises(SchemaError, match="layout"):
        decode_kv(alien)
    # Untampered frames still round-trip, and error frames (which carry
    # no layout) still decode as error replies.
    dec, meta = decode_kv(frames)
    assert meta["layout"] == "latent"
    err, emeta = decode_kv([encode_error("g", "boom")])
    assert err is None and emeta["error"] == "boom"


def test_schema_error_maps_to_schema_reply_code():
    """Workers answer schema skew with the typed ``schema`` error code
    (the fix is an upgrade, not a retry) — everything else keeps the
    repr() diagnostic."""
    from distributed_llm_inference_tpu.disagg.decode_node import _err_code

    assert _err_code(SchemaError("unsupported kv codec version")) == "schema"
    assert _err_code(ValueError("crc mismatch")) == repr(
        ValueError("crc mismatch"))


# -- speculative A/B normalization --------------------------------------------


def test_spec_adapt_normalizes_per_spec_row():
    """Two windows at different speculative occupancy but identical
    per-row throughput must fold to the SAME rate: the controller
    normalizes by active speculative rows, so batch occupancy cannot
    masquerade as a mode speedup."""
    eng = InferenceEngine(
        BASE_CFG, _params(BASE_CFG),
        EngineConfig(max_batch_size=4, prefill_buckets=(8,),
                     max_seq_len=64, dtype="float32", speculative_k=2,
                     speculative_probe_len=2),
        CacheConfig(kind="dense"),
        draft=(BASE_CFG, _params(BASE_CFG)),
    )
    clock = {"t": 0.0}
    tokens = {"n": 0.0}
    eng._spec_clock = lambda: clock["t"]
    eng._decode_tokens_total = lambda: tokens["n"]
    eng._session_wants_spec = lambda s: True

    def window(nspec, tok_per_row):
        """Drive one full measurement window at ``nspec`` occupancy."""
        eng.slots = [f"g{i}" for i in range(nspec)] + [None]
        eng.sessions = {f"g{i}": object() for i in range(nspec)}
        c = eng._spec_ctl
        c["comp"] = tuple(eng.slots)  # composition stable within window
        c.update(win_t0=clock["t"], win_tok0=tokens["n"], win_ticks=0,
                 stat0=dict(eng.spec_stats), skip=0)
        for _ in range(2):  # probe_len=2 ticks close the window
            clock["t"] += 1.0
            tokens["n"] += nspec * tok_per_row
            eng._spec_adapt([])
        return eng._spec_ctl["spec_rate"]

    r1 = window(nspec=3, tok_per_row=5.0)
    assert r1 == pytest.approx(5.0)  # tokens/s PER ROW, not 15.0 batch-wide
    eng._spec_ctl["spec_rate"] = None  # independent second measurement
    r2 = window(nspec=1, tok_per_row=5.0)
    assert r2 == pytest.approx(r1)  # occupancy change ⇒ same normalized rate

    # Full disengagement resets the window baseline.
    eng.slots = [None] * 4
    eng.sessions = {}
    eng._spec_adapt([])
    assert eng._spec_ctl["win_t0"] is None


# -- the write-behind tail of the int8 latent pool ------------------------------
#
# ``QuantizedLatentPagedKVCache`` decodes through the one-stored-plane form of
# ``quantized_paged_fused_attention`` (ops/paged_attention.py): the fused
# sweep's body with ONE pool operand, one page buffer, one tail plane. A
# stored row of whole 128-lane tiles, or one narrower than a tile (every pool
# of this suite but the 160-wide one), is swept by the kernel's own async
# copies; a row like the latent pool's 576, which Mosaic cannot slice, gets
# its pages as pipelined blocks (``_pages_by_grid``): 160 = 128 + 32 is that
# row at a size the interpreter walks in seconds.

import numpy as np

from distributed_llm_inference_tpu.cache.dense import _quantize_kv
from distributed_llm_inference_tpu.cache.latent import (
    QuantizedLatentPagedKVCache,
)
from distributed_llm_inference_tpu.ops import paged_attention as pa

_TAIL = dict(ps=16, t=5, kt=16, g=4, layers=2, layer=1, scale=0.2)


def _tail_rows(rows):
    """(pool length, decoding) a row: a released slot whose length is stale
    (it sweeps nothing), one token, a part of a page, several pages (a block
    and a partial one), and all the table holds beside the window's tail."""
    ps, t, kt = _TAIL["ps"], _TAIL["t"], _TAIL["kt"]
    kinds = [(3 * ps + 2, False), (1, True), (ps - 3, True),
             (3 * ps + 2, True), (t * ps - kt, True)]
    return {1: kinds[3:4], 2: [kinds[0], kinds[4]], 5: kinds}[rows]


def _tail_inputs(rows, d, step, seed=0):
    f = _TAIL
    ps, t, kt, g, layers = f["ps"], f["t"], f["kt"], f["g"], f["layers"]
    rng = np.random.default_rng([seed, rows, d, step])
    lens, decoding = map(np.asarray, zip(*_tail_rows(rows)))
    pages = rows * t + 1
    # every page no live token owns, and every slot past a row's length, is
    # poison: a dead page read, or a dead slot unmasked, shows
    pool = np.full((layers, pages, 1, ps, d), 127, np.int8)
    scales = np.full((layers, pages, 1, ps), 1e30, np.float32)
    table = np.zeros((rows, t), np.int32)
    ids = rng.permutation(np.arange(1, pages))
    for r in range(rows):
        table[r] = ids[r * t:(r + 1) * t]
        for pos in range(lens[r]):
            pool[:, table[r, pos // ps], 0, pos % ps] = rng.integers(
                -127, 128, (layers, d))
            scales[:, table[r, pos // ps], 0, pos % ps] = rng.uniform(
                0.01, 0.03, layers)
    tail = rng.integers(-127, 128, (layers, rows, 1, kt, d)).astype(np.int8)
    tail_s = rng.uniform(0.01, 0.03, (layers, rows, 1, kt)).astype(np.float32)
    return dict(
        q=jnp.asarray(rng.normal(size=(rows, 1, g, d)), jnp.bfloat16),
        c_new=jnp.asarray(rng.normal(size=(rows, 1, 1, d)), jnp.bfloat16),
        pool_c=jnp.asarray(pool), pool_cs=jnp.asarray(scales),
        tail_c=jnp.asarray(tail), tail_cs=jnp.asarray(tail_s),
        layer_idx=f["layer"], step_idx=step, page_table=jnp.asarray(table),
        base_len=jnp.asarray(lens, jnp.int32),
        tail_valid_len=jnp.asarray(np.where(decoding, step + 1, 0), jnp.int32),
        q_positions=jnp.asarray(lens + step, jnp.int32), scale=f["scale"],
    )


def _tail_oracle(a, tail_c, tail_cs):
    """float32 softmax over a decoding row's live pool positions and valid
    tail slots, dequantised; zeros for a row that is not decoding."""
    ps, layer = _TAIL["ps"], _TAIL["layer"]
    pool, scales = np.asarray(a["pool_c"]), np.asarray(a["pool_cs"])
    table, lens = np.asarray(a["page_table"]), np.asarray(a["base_len"])
    vlen = np.asarray(a["tail_valid_len"])
    q = np.asarray(a["q"].astype(jnp.float32))
    out = np.zeros(q.shape, np.float32)
    for r in range(q.shape[0]):
        if not vlen[r]:
            continue
        kv = [pool[layer, table[r, p // ps], 0, p % ps].astype(np.float32)
              * scales[layer, table[r, p // ps], 0, p % ps]
              for p in range(lens[r])]
        kv += [np.asarray(tail_c[layer, r, 0, i], np.float32)
               * float(tail_cs[layer, r, 0, i]) for i in range(vlen[r])]
        kv = np.stack(kv)
        s = q[r, 0] @ kv.T * a["scale"]
        p = np.exp(s - s.max(-1, keepdims=True))
        out[r, 0] = (p / p.sum(-1, keepdims=True)) @ kv
    return out


@pytest.mark.parametrize("step", [0, 7, 15])
@pytest.mark.parametrize("d", [24, 160], ids=["copied-pages", "pipelined-pages"])
@pytest.mark.parametrize("rows", [1, 2, 5])
def test_one_plane_fused_decode_matches_reference_and_grid_form(rows, d, step):
    a = _tail_inputs(rows, d, step)
    assert pa._pages_by_grid(d) == (d == 160)
    out, tail_c, tail_cs = pa.quantized_latent_paged_fused_attention(**a)
    layer = _TAIL["layer"]
    # the step's latent lands at its slot, quantised by the pool's own rule;
    # the other slots and the other layer are as they were
    # (jitted, as the one-token path's scatter runs it: compiled, XLA turns
    # ``/ 127`` into a multiply)
    want_c, want_cs = jax.jit(_quantize_kv)(a["c_new"])
    np.testing.assert_array_equal(tail_c[layer, :, :, step], want_c[:, 0])
    np.testing.assert_array_equal(tail_cs[layer, :, :, step], want_cs[:, 0])
    keep = np.arange(_TAIL["kt"]) != step
    np.testing.assert_array_equal(
        np.asarray(tail_c)[layer][:, :, keep], np.asarray(a["tail_c"])[layer][:, :, keep])
    np.testing.assert_array_equal(tail_c[1 - layer], a["tail_c"][1 - layer])
    np.testing.assert_array_equal(tail_cs[1 - layer], a["tail_cs"][1 - layer])

    got = np.asarray(out.astype(jnp.float32))
    assert np.isfinite(got).all(), "a dead page or a dead slot was read"
    decoding = np.asarray(a["tail_valid_len"]) > 0
    assert (got[~decoding] == 0).all(), "a released row attends to nothing"
    # bf16 operands into f32 sums, the probabilities rounded to 8 bits before
    # they meet the values (|value| <= 127 * 0.03)
    np.testing.assert_allclose(
        got, _tail_oracle(a, tail_c, tail_cs), atol=0.03, rtol=0.02)

    # the grid form (one layer's pool, already written, every tile of slots x
    # table width, float32 sums): flush the tail as the engine would and ask it
    tail_len = a["tail_valid_len"]
    new_c, new_cs = pa.paged_tail_flush(
        a["pool_c"], a["pool_cs"], None, None, tail_c, tail_cs, None, None,
        a["page_table"], a["base_len"], tail_len,
    )
    grid = pa.quantized_latent_paged_attention(
        a["q"], new_c[layer], new_cs[layer], a["page_table"],
        jnp.where(tail_len > 0, a["base_len"] + tail_len, 0), scale=a["scale"],
    )
    np.testing.assert_allclose(
        got, np.asarray(grid.astype(jnp.float32)), atol=0.03, rtol=0.02)


@pytest.mark.parametrize("page_size", [8, 16], ids=["scattered", "page-rmw"])
def test_one_plane_tail_flush_writes_what_the_one_token_path_writes(page_size):
    """The window's tail lands in the pool at each row's next positions, the
    slots past a row's tail length and the rows that wrote nothing left as
    they were: a 16-slot tail over pages of 16 (the blocked page
    read-modify-write, one plane) and over pages of 8 (the scatter)."""
    layers, rows, t, kt, d = 2, 3, 6, 16, 24
    rng = np.random.default_rng(page_size)
    cache = QuantizedLatentPagedKVCache.create(
        layers, rows, rows * t + 1, page_size, t, 1, d, use_kernel=True)
    ids = rng.permutation(np.arange(1, rows * t + 1)).reshape(rows, t)
    lens = np.asarray([page_size - 3, 0, 2 * page_size + 1], np.int32)
    wrote = np.asarray([kt, 0, 5], np.int32)
    cache = cache.replace(
        k_pages=jnp.asarray(rng.integers(-127, 128, cache.k_pages.shape), jnp.int8),
        cs_pages=jnp.asarray(rng.uniform(0.01, 0.03, cache.cs_pages.shape), jnp.float32),
        page_table=jnp.asarray(ids, jnp.int32), lengths=jnp.asarray(lens),
    )
    tail_c = rng.integers(-127, 128, (layers, rows, 1, kt, d)).astype(np.int8)
    tail_cs = rng.uniform(0.01, 0.03, (layers, rows, 1, kt)).astype(np.float32)
    want_c, want_cs = np.asarray(cache.k_pages).copy(), np.asarray(cache.cs_pages).copy()
    for r in range(rows):
        for i in range(wrote[r]):
            pos = lens[r] + i
            want_c[:, ids[r, pos // page_size], 0, pos % page_size] = tail_c[:, r, 0, i]
            want_cs[:, ids[r, pos // page_size], 0, pos % page_size] = tail_cs[:, r, 0, i]
    new = cache.tail_flush((jnp.asarray(tail_c), jnp.asarray(tail_cs)), jnp.asarray(wrote))
    # page 0 is the null page: diverted writes may land there
    np.testing.assert_array_equal(np.asarray(new.k_pages)[:, 1:], want_c[:, 1:])
    np.testing.assert_array_equal(np.asarray(new.cs_pages)[:, 1:], want_cs[:, 1:])
    np.testing.assert_array_equal(new.lengths, lens + wrote)


def _kernel_cache(cfg, page_size, rows=2, slots=8):
    cache = QuantizedLatentPagedKVCache.create(
        cfg.num_layers, rows, rows * slots + 1, page_size, slots, 1,
        cfg.latent.lat_dim, use_kernel=True)
    for r in range(rows):
        cache = cache.assign_pages(r, list(range(1 + r * slots, 1 + (r + 1) * slots)))
    return cache


@pytest.mark.parametrize("page_size", [8, 16], ids=["scattered", "page-rmw"])
@pytest.mark.parametrize(
    "layers,latent", [(1, MLA_CFG.latent), (2, MLA_CFG.latent),
                      (2, LatentConfig(rank=128, rope_head_dim=32))],
    ids=["1", "2", "2-pipelined-pages"],
)
def test_sixteen_fused_steps_are_sixteen_one_token_steps(layers, latent, page_size):
    """``multi_decode_apply`` over the int8 latent pool (the kernel,
    interpreted) against 16 ``model_apply`` steps over the same pool: the same
    tokens, and after ``tail_flush`` the same pool. Bit for bit where what is
    stored does not pass through attention first (a layer's latent is a
    function of its input, so the first layer's, and every layer's scales'
    and values' where no value sat on a rounding edge); the fused body rounds
    its probabilities to bf16 where the grid form keeps float32, so a deeper
    layer's latents may differ by one step of the int8 grid."""
    import dataclasses

    cfg = dataclasses.replace(MLA_CFG, num_layers=layers, latent=latent)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    cache = _kernel_cache(cfg, page_size)
    assert cache.has_tail and cache.tail_in_kernel and cache.tail_reads_whole_big
    # a 160-wide stored row comes as pipelined blocks, walked by the list
    # the model builds once for the window's 16 steps x layers
    assert (cache.tail_walk(16, cache.lengths, cache.lengths + 1) is None) == (
        latent.lat_dim != 160)
    prompts = jnp.asarray([[3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37],
                           [2, 4, 6, 8, 10, 0, 0, 0, 0, 0, 0]], jnp.int32)
    n_valid = jnp.asarray([11, 5], jnp.int32)
    logits, cache = llama.model_apply(cfg, params, prompts, cache, n_valid, head="last")
    first = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
    one = jnp.ones((2,), jnp.int32)

    def token(carry, _):
        tok, cache = carry
        logits, cache = llama.model_apply(cfg, params, tok[:, None], cache, one)
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return (nxt, cache), (nxt, logits[:, 0])

    (_, stepped), (want, want_logits) = jax.jit(
        lambda c: jax.lax.scan(token, (first, c), None, length=16))(cache)

    def step_fn(i, logits, state):
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, one, state, (nxt, logits)

    (got, got_logits), fused = jax.jit(lambda c: llama.multi_decode_apply(
        cfg, params, first[:, None], c, 16, step_fn, jnp.zeros(()), one))(cache)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(got_logits, want_logits, atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(fused.lengths, stepped.lengths)
    a_c, b_c = np.asarray(fused.k_pages)[:, 1:], np.asarray(stepped.k_pages)[:, 1:]
    a_s, b_s = np.asarray(fused.cs_pages)[:, 1:], np.asarray(stepped.cs_pages)[:, 1:]
    np.testing.assert_array_equal(a_c[0], b_c[0])
    np.testing.assert_array_equal(a_s[0], b_s[0])
    assert np.abs(a_c.astype(np.int32) - b_c).max() <= 1
    np.testing.assert_allclose(a_s, b_s, rtol=2e-2)
    assert (a_c != b_c).mean() < 0.02


def _pallas_call(fn, *args):
    (eqn,) = [e for e in jax.make_jaxpr(fn)(*args).eqns
              if e.primitive.name == "pallas_call"]
    return eqn


@pytest.mark.parametrize("planes", [2, 1])
def test_the_fused_kernels_operands_by_stored_planes(planes):
    """The two-plane call is the call it was: two pool operands left in HBM,
    two page buffers beside the semaphores and the three softmax scratches,
    four tail planes aliased to their outputs. One stored plane halves each."""
    s = jax.ShapeDtypeStruct
    b, t, hkv, g, d, ps, kt, layers, pages = 3, 6, 2, 2, 128, 16, 4, 2, 8
    pool = (s((layers, pages, hkv, ps, d), jnp.int8), s((layers, pages, hkv, ps), jnp.float32))
    tail = (s((layers, b, hkv, kt, d), jnp.int8), s((layers, b, hkv, kt), jnp.float32))
    new = s((b, 1, hkv, d), jnp.bfloat16)
    rest = (s((), jnp.int32), s((), jnp.int32), s((b, t), jnp.int32),
            s((b,), jnp.int32), s((b,), jnp.int32), s((b,), jnp.int32))
    if planes == 2:
        def call(q, kn, vn, pk, pks, pv, pvs, tk, tks, tv, tvs, *r):
            return pa.quantized_paged_fused_attention(
                q, kn, vn, pk, pks, pv, pvs, tk, tks, tv, tvs, *r, interpret=True)

        operands = (new, new, *pool, *pool, *tail, *tail)
    else:
        def call(q, kn, pk, pks, tk, tks, *r):
            return pa.quantized_paged_fused_attention(
                q, kn, None, pk, pks, None, None, tk, tks, None, None, *r,
                interpret=True)

        operands = (new, *pool, *tail)
    eqn = _pallas_call(call, s((b, 1, hkv * g, d), jnp.bfloat16), *operands, *rest)
    gm = eqn.params["grid_mapping"]
    # an operand left in HBM is the whole array, in no memory space of the
    # kernel's own
    in_hbm = [m for m in gm.block_mappings[:gm.num_inputs]
              if m.block_aval.shape == (layers, pages, hkv, ps, d)
              and "any" in str(m.block_aval)]
    first_tail = 6 + 1 + planes
    assert gm.grid == (b,)
    assert len(eqn.invars) == 6 + 1 + 5 * planes
    assert len(eqn.outvars) == 1 + 2 * planes
    assert gm.num_scratch_operands == planes + 4
    assert tuple(eqn.params["input_output_aliases"]) == tuple(
        (first_tail + i, 1 + i) for i in range(2 * planes))
    assert len(in_hbm) == planes, [str(m.block_aval) for m in gm.block_mappings]


# -- the walk of the latent pool's decode sweep (ISSUE 48) ----------------------
#
# Where a pool's pages come as pipelined blocks the grid is ONE axis over the
# call's live steps: for each row the blocks of ``n`` table slots that hold a
# live page, a row with none keeping one step (``pa._sweep_walk``). The
# oracle is this file's own: each row's blocks walked in the table's order
# with plain ``jax.numpy``, a block one tile of the online softmax in the
# kernel's arithmetic (bf16 operands into float32 sums), then the tail's
# tile. Under a selection the kernel still steps through rows x every block
# of the table (its program the parent's): the same plain walk is what it
# must give, a block with no live page changing no sum.

_WALK = dict(ps=16, kt=16, g=4, d=160, layers=2, layer=1, step=3, scale=0.2)
_PS = _WALK["ps"]
# case -> (table width, (pool length, decoding) a row); 8 pages a block at
# these shapes wherever the table is that wide
_WALK_CASES = {
    "idle-row-between-two": (19, [(5 * _PS + 2, 1), (3 * _PS, 0), (9 * _PS - 1, 1)]),
    "every-row-idle": (19, [(5 * _PS + 2, 0), (3 * _PS, 0)]),
    "n-pages-and-n-plus-1": (19, [(8 * _PS, 1), (8 * _PS + 1, 1), (1, 1)]),
    "table-no-multiple-of-n": (11, [(11 * _PS - 16, 1), (4 * _PS + 5, 1)]),
    "row-fills-its-table": (16, [(16 * _PS, 1), (16 * _PS - 16, 1)]),
}


def _walk_inputs(case, seed=0):
    f = _WALK
    t, kinds = _WALK_CASES[case]
    ps, kt, g, d, layers = f["ps"], f["kt"], f["g"], f["d"], f["layers"]
    rows = len(kinds)
    lens, decoding = map(np.asarray, zip(*kinds))
    rng = np.random.default_rng([seed, t, rows])
    pages = rows * t + 1
    a = dict(
        q=jnp.asarray(rng.normal(size=(rows, 1, g, d)), jnp.bfloat16),
        c_new=jnp.asarray(rng.normal(size=(rows, 1, 1, d)), jnp.bfloat16),
        pool_c=jnp.asarray(rng.integers(-127, 128, (layers, pages, 1, ps, d)), jnp.int8),
        pool_cs=jnp.asarray(rng.uniform(0.01, 0.03, (layers, pages, 1, ps)), jnp.float32),
        tail_c=jnp.asarray(rng.integers(-127, 128, (layers, rows, 1, kt, d)), jnp.int8),
        tail_cs=jnp.asarray(rng.uniform(0.01, 0.03, (layers, rows, 1, kt)), jnp.float32),
        layer_idx=f["layer"], step_idx=f["step"],
        page_table=jnp.asarray(
            rng.permutation(np.arange(1, pages)).reshape(rows, t), jnp.int32),
        base_len=jnp.asarray(lens, jnp.int32),
        tail_valid_len=jnp.asarray(np.where(decoding, f["step"] + 1, 0), jnp.int32),
        q_positions=jnp.asarray(lens + f["step"], jnp.int32), scale=f["scale"],
    )
    select = (
        jnp.asarray(rng.integers(0, 2, (rows, t, 1, ps)), jnp.float32),
        jnp.asarray(rng.integers(0, 2, (rows, 1, kt)), jnp.float32),
    )
    return a, select


def _plain_walk(a, select, n, tail_c, tail_cs):
    """The rows' results by a plain walk: row by row, its blocks
    ``[0, cdiv(live pages, n))`` in order (none where it is not decoding),
    a dead place the null page under the mask, then the tail as the kernel
    left it, then the division."""
    f = _WALK
    ps, kt, g, layer, scale = f["ps"], f["kt"], f["g"], f["layer"], f["scale"]
    table, lens = np.asarray(a["page_table"]), np.asarray(a["base_len"])
    vlen = np.asarray(a["tail_valid_len"])
    t = table.shape[1]
    pool, scales = a["pool_c"][layer], a["pool_cs"][layer]
    neg = pa._NEG_INF

    def tile(state, qb, kk, kks, valid):
        acc, m, l = state
        kb = kk.astype(jnp.bfloat16)[None]
        s = jax.lax.dot_general(
            qb, kb, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s = (s * kks[:, None, :] * scale).reshape(g, -1)
        s = jnp.where(valid, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            (p.reshape(1, g, -1) * kks[:, None, :]).astype(jnp.bfloat16), kb,
            (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32)
        return acc * alpha + pv.reshape(g, -1), m_new, l

    def row(r):
        kv_len = int(lens[r]) if vlen[r] else 0
        hi = min(-(-kv_len // ps), t)
        qb = a["q"][r, 0].astype(jnp.bfloat16)[None]
        state = (jnp.zeros((g, qb.shape[-1]), jnp.float32),
                 jnp.full((g, 1), neg, jnp.float32), jnp.zeros((g, 1), jnp.float32))
        for blk in range(-(-hi // n)):
            places = range(blk * n, blk * n + n)
            slots = [min(p, t - 1) for p in places]
            kk = jnp.concatenate(
                [pool[table[r, p] if p < hi else 0, 0] for p in places], 0)
            kks = jnp.concatenate([scales[table[r, s]] for s in slots], -1)
            valid = (blk * n * ps + jnp.arange(n * ps))[None] < kv_len
            if select is not None:
                valid &= jnp.concatenate(
                    [select[0][r, s] for s in slots], -1) > 0
            state = tile(state, qb, kk, kks, valid)
        valid = jnp.arange(kt)[None] < vlen[r]
        if select is not None:
            valid &= select[1][r] > 0
        acc, _, l = tile(
            state, qb, tail_c[layer, r, 0], tail_cs[layer, r], valid)
        return (acc / jnp.maximum(l, 1e-20)).astype(a["q"].dtype)

    return jnp.stack([row(r) for r in range(len(lens))])[:, None]


@pytest.mark.parametrize("selected", [False, True], ids=["all-keys", "selection"])
@pytest.mark.parametrize("case", list(_WALK_CASES))
def test_the_walked_sweep_is_a_plain_walk_of_the_same_blocks_bit_for_bit(
        case, selected):
    a, select = _walk_inputs(case)
    select = select if selected else None
    t = a["page_table"].shape[1]
    n = pa._pages_per_block(t, 1, _WALK["ps"], _WALK["d"], _WALK["kt"], 1)
    assert pa._pages_by_grid(_WALK["d"]) and n == 8
    out, tail_c, tail_cs = pa.quantized_latent_paged_fused_attention(
        **a, select=select)
    # compiled, as the interpreter compiles the kernel's body
    want = jax.jit(lambda tc, tcs: _plain_walk(a, select, n, tc, tcs))(tail_c, tail_cs)
    got = np.asarray(out.astype(jnp.float32))
    np.testing.assert_array_equal(got, np.asarray(want.astype(jnp.float32)))
    decoding = np.asarray(a["tail_valid_len"]) > 0
    assert (got[~decoding] == 0).all() and np.abs(got[decoding]).min(axis=(1, 2, 3)).all()


@pytest.mark.parametrize("window", [None, 40], ids=["every-key", "window-40"])
@pytest.mark.parametrize("n", [4, 8])
def test_the_walk_names_each_live_block_once_and_in_row_order(n, window):
    """The wrapper's list (``jax.numpy``) is its host twin's (``numpy``, what
    ``engine/plan.py`` counts by), and both are the rows' live blocks by
    ``_live_pages`` written out in loops: a row's blocks in the table's
    order, the rows in theirs, one step for a row with no live block, a dead
    place the null page, and past the walked steps the last one again."""
    ps, t, rows = 16, 19, 7
    rng = np.random.default_rng([n, window or 0])
    lens = np.asarray([0, 5 * ps + 2, t * ps, 3 * ps, 8 * ps, 8 * ps + 1, 12 * ps - 1])
    vlen = np.asarray([2, 2, 2, 0, 2, 2, 2])
    qpos = lens + 1
    table = rng.permutation(np.arange(1, rows * t + 1)).reshape(rows, t).astype(np.int32)
    host = pa._sweep_walk(table, lens, vlen, qpos, n, ps, window, np)
    device = pa._sweep_walk(
        *(jnp.asarray(x, jnp.int32) for x in (table, lens, vlen, qpos)), n, ps, window)
    for h, d in zip(host, device):
        np.testing.assert_array_equal(h, np.asarray(d))
    want = []
    for r in range(rows):
        lo, hi = pa._live_pages(
            lens[r] if vlen[r] else 0, qpos[r], ps, t, window, np)
        for blk in range(lo // n, max(-(-hi // n), lo // n + 1)):
            want.append((r, blk, [
                table[r, p] if lo <= p < hi else 0
                for p in range(blk * n, blk * n + n)]))
    steps, at, blocks, pages = host
    assert steps == len(want) < rows * -(-t // n) == len(at) == len(blocks)
    want += [want[-1]] * (len(at) - len(want))
    assert [(r, b) for r, b, _ in want] == list(zip(at.tolist(), blocks.tolist()))
    np.testing.assert_array_equal(pages.reshape(-1, n), [p for _, _, p in want])
    assert len(set(zip(at[:steps].tolist(), blocks[:steps].tolist()))) == steps


@pytest.mark.parametrize("rank,rope", [(16, 8), (128, 32)],
                         ids=["copied-pages", "pipelined-pages"])
def test_an_engine_counts_the_steps_its_latent_sweep_walks(rank, rope):
    """An int8 latent engine whose stored row comes as pipelined blocks
    (160 wide here, 576 in the cells) tells its plan (``walked_pool``), and a
    decode dispatch adds to ``decode_sweep_steps_walked`` /
    ``decode_sweep_steps_grid``; a row the copies sweep counts neither."""
    import dataclasses

    cfg = dataclasses.replace(
        MLA_CFG, latent=LatentConfig(rank=rank, rope_head_dim=rope))
    eng = InferenceEngine(
        cfg, llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16, 32),
                     max_seq_len=256, dtype="float32",
                     use_pallas_attention=True),
        CacheConfig(kind="paged", kv_quant="int8", page_size=PS, num_pages=64,
                    max_pages_per_session=16),
        rng=jax.random.PRNGKey(1),
    )
    piped = rank == 128
    assert eng.decode_steps == 16
    assert eng.plan.walked_pool == ((1, rank + rope) if piped else None)
    eng.generate([list(range(3, 3 + 9 * PS))], SamplingOptions(max_new_tokens=20))
    assert eng.cache.page_table.shape[1] == 12
    walked = eng.metrics.get_counter("decode_sweep_steps_walked")
    grid = eng.metrics.get_counter("decode_sweep_steps_grid")
    if not piped:
        assert walked == grid == 0
        return
    # 12 table slots are two blocks of 8 a row: the live row's 10 or 11 pages
    # take both, the idle row one step, of 2 x 2 every step of a window
    assert grid > 0 and walked * 4 == grid * 3 and grid % (16 * 4) == 0
    text = eng.metrics.prometheus()
    assert "decode_sweep_steps_walked_total" in text
    assert "decode_sweep_steps_grid_total" in text
