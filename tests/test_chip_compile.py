"""The serving path's kernels compile for a v5e at Mistral-7B widths.

Interpret mode cannot see what the chip's compiler refuses: a scratch that
overflows a core's 16 MiB of scoped VMEM (the ragged kernels at 32 query / 8
kv heads of 128 with the old fixed ``block_q`` of 128), or a cast Mosaic no
longer lowers (the fused int8 decode kernels' mask squeeze under jax 0.9.0).
The TPU compiler is installed beside the CPU backend and compiles for a chip
that is described, not attached — about a second or two a kernel, no chip
time. Nothing here runs, so nothing here says a kernel is right or fast.

Skipped as one where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
# libtpu lets one process load it at a time (a lockfile): no chip is held
# here, and under pytest-xdist every worker may get some of these cases.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_llm_inference_tpu.ops import flash_attention as fa
from distributed_llm_inference_tpu.ops import paged_attention as pa
from distributed_llm_inference_tpu.ops import ragged_attention as ra

# Mistral-7B-v0.1: 32 query / 8 kv heads of 128; the engine's defaults:
# 64-token pages, a 512-page pool, 64 table slots, 16-step fused decode.
HQ, HKV, D, PS, PAGES, SLOTS, LAYERS, KT = 32, 8, 128, 64, 512, 64, 32, 16
I8, F32, I32 = jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def chip(compiles_cold):
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or it cannot describe a v5e here
        pytest.skip(f"cannot describe a v5e topology: {e!r}")
    one = SingleDeviceSharding(topo.devices[0])
    # A described-device executable written to the persistent cache cannot
    # be read back without a chip (the next compile warns): keep it off,
    # whatever the session set. And compile at the chip's own matmul
    # precision, not the "highest" the CPU suite asks for in conftest
    # (Mosaic has no fp32-precision matmul over bf16 operands).
    was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    with compiles_cold():
        yield lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one
        )
    jax.config.update("jax_default_matmul_precision", was)


def _compiles_with_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled step"


def _pool(s, dtype):
    pages = s((PAGES, HKV, PS, D), dtype)
    return (pages, pages) if dtype != I8 else (
        pages, s((PAGES, HKV, PS), F32), pages, s((PAGES, HKV, PS), F32)
    )


@pytest.mark.parametrize("seq,batch", [(128, 4), (2048, 1)])
@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("pages", ["model-dtype", "int8"])
def test_ragged_prefill_kernels(chip, seq, batch, q_dtype, pages):
    """Both ragged kernels with the block_q they choose for themselves."""
    s = chip
    rows = (s((batch, SLOTS), I32), s((batch,), I32), s((batch,), I32))
    q = s((batch, seq, HQ, D), q_dtype)
    kernel = (
        ra.quantized_ragged_paged_attention if pages == "int8"
        else ra.ragged_paged_attention
    )
    _compiles_with_kernel(
        lambda q, *a: kernel(q, *a, sliding_window=4096, interpret=False),
        q, *_pool(s, I8 if pages == "int8" else q_dtype), *rows,
    )


@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_paged_decode_kernels(chip, pages):
    s, b = chip, 8
    kernel = (
        pa.quantized_paged_attention if pages == "int8" else pa.paged_attention
    )
    _compiles_with_kernel(
        lambda q, *a: kernel(q, *a, sliding_window=4096, interpret=False),
        s((b, 1, HQ, D), jnp.bfloat16),
        *_pool(s, I8 if pages == "int8" else jnp.bfloat16),
        s((b, SLOTS), I32), s((b,), I32),
    )


@pytest.mark.parametrize(
    "rows,width,pages,window,hkv,layers",
    [
        (8, SLOTS, PAGES, 4096, HKV, LAYERS),     # the engine's defaults
        (32, 38, 1280, 4096, HKV, LAYERS),  # mistral-7b.reason's pinned table
        (32, 47, 1280, 4096, HKV, LAYERS),  # mistral-7b.chat's
        (16, 64, 1024, None, HKV, LAYERS),  # mixtral-8x7b-8l.rag: no window
        (32, 64, 1280, None, HKV, LAYERS),
        (16, 16, 160, None, 16, 192),       # ouro-2.6b.mathchat: 2 pages a block
        (32, 227, 512, 128, HKV, 12),       # mixedlen's window pool: 1 a block
    ],
)
@pytest.mark.parametrize("scales", ["joined", "as-stored"])
def test_fused_int8_paged_decode_kernel(
    chip, rows, width, pages, window, hkv, layers, scales
):
    """At the shapes the engine's decode scan gives it in the benchmark's
    cells: the whole ``[L, P, ...]`` pool, a 16-slot tail, rank-0 layer and
    step indices, and the pages a block the kernel picks for itself — so a
    block over the scoped VMEM, or an operand Mosaic refuses, fails here.
    ``joined``: K's and V's scale rows of a page in ONE plane of 128 lanes,
    which the kernel copies by the live page (what every cell's cache hands
    it since PR 61: no gather is compiled beside the kernel); ``as-stored``:
    the two planes of 64 lanes, whose rows the wrapper gathers (the
    fall-back by the shape)."""
    s, b = chip, rows
    bf16 = jnp.bfloat16
    plane = s((layers, pages, hkv, PS, D), I8)
    tail = (s((layers, b, hkv, KT, D), I8), s((layers, b, hkv, KT), F32))
    joined = jax.eval_shape(
        pa.joined_scale_rows, *[s((layers, pages, hkv, PS), F32)] * 2
    )
    stored = s(joined.shape[:-1] + (PS,), F32)
    pool_ks, pool_vs = (
        (s(joined.shape, F32), None) if scales == "joined" else (stored, stored)
    )
    compiled = jax.jit(
        lambda *a: pa.quantized_paged_fused_attention(
            *a, sliding_window=window, interpret=False
        )
    ).lower(
        s((b, 1, hkv * 4, D), bf16), s((b, 1, hkv, D), bf16),
        s((b, 1, hkv, D), bf16), plane, pool_ks, plane, pool_vs,
        *tail, *tail, s((), I32), s((), I32),
        s((b, width), I32), s((b,), I32), s((b,), I32), s((b,), I32),
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled step"
    gathered = f"f32[{b * width},{hkv},{PS}]"
    assert (gathered in text) == (scales == "as-stored")


# moonlight-16b-a3b.reason1k: 16 query heads on ONE latent head, the stored
# row 512 + 64 = 576 wide (not a multiple of the 128 lanes), int8 with a
# float32 scale a token, 1792 pages of 64, 32 slots.
LAT_HQ, LAT_W, LAT_PAGES, LAT_LAYERS = 16, 576, 1792, 16


def _latent_pool(s, pool):
    pages = s((LAT_PAGES, 1, PS, LAT_W), I8 if pool == "int8" else F32)
    return (pages, s((LAT_PAGES, 1, PS), F32)) if pool == "int8" else (pages,)


@pytest.mark.parametrize("width", [59, SLOTS])   # the cell's pinned table; the cap
@pytest.mark.parametrize("pool", ["int8", "f32"])
def test_latent_decode_kernels_at_moonlights_shapes(chip, pool, width):
    """Both latent decode wrappers take the 576-wide row as it is stored:
    Mosaic pads the lanes itself, nothing had to change in the layout."""
    s, b = chip, 32
    kernel = (
        pa.quantized_latent_paged_attention if pool == "int8"
        else pa.latent_paged_attention
    )
    _compiles_with_kernel(
        lambda q, *a: kernel(q, *a, scale=192 ** -0.5, interpret=False),
        s((b, 1, LAT_HQ, LAT_W), jnp.bfloat16), *_latent_pool(s, pool),
        s((b, width), I32), s((b,), I32),
    )


@pytest.mark.parametrize("width", [59, SLOTS])   # the cell's pinned table; the cap
@pytest.mark.parametrize(
    "heads,layers", [(LAT_HQ, LAT_LAYERS), (32, 13)],
    ids=["moonlight-16b-a3b", "xing4.0-29b-a4b"],
)
def test_fused_latent_decode_kernel_at_reason1ks_shapes(chip, heads, layers, width):
    """The one-stored-plane form of the fused int8 decode kernel as the two
    ``reason1k`` cells' decode scans give it: the WHOLE latent pool in HBM
    (a ``[1, 64, 576]`` int8 page out of it a pipelined operand, eight a
    step), 32 rows, a 16-slot tail, bf16 matmuls over the 576-wide row as
    stored, its grid ONE dynamic axis over the steps of the walk that the
    window built once (``latent_sweep_walk``: models/llama.py)."""
    s, b = chip, 32

    def step(q, c_new, pool_c, pool_cs, tail_c, tail_cs, layer, at, table,
             lens, vlen, qpos):
        walk = pa.latent_sweep_walk(pool_c, KT, table, lens, vlen)
        assert walk is not None and walk[1].shape == (b * -(-width // 8),)
        return pa.quantized_latent_paged_fused_attention(
            q, c_new, pool_c, pool_cs, tail_c, tail_cs, layer, at, table,
            lens, vlen, qpos, scale=192 ** -0.5, interpret=False, walk=walk,
        )

    _compiles_with_kernel(
        step,
        s((b, 1, heads, LAT_W), jnp.bfloat16),
        s((b, 1, 1, LAT_W), jnp.bfloat16),
        s((layers, LAT_PAGES, 1, PS, LAT_W), I8),
        s((layers, LAT_PAGES, 1, PS), F32),
        s((layers, b, 1, KT, LAT_W), I8), s((layers, b, 1, KT), F32),
        s((), I32), s((), I32),
        s((b, width), I32), s((b,), I32), s((b,), I32), s((b,), I32),
    )


def test_a_per_head_pools_call_keeps_the_copies_form():
    """The walk is the pipelined blocks' alone (``_pages_by_grid``: a stored
    row Mosaic cannot copy). A per-head pool's call at ``mistral-7b.reason``'s
    shapes is the call it was: a grid over the rows, both pools whole in HBM
    (``pl.ANY``), six scalar-prefetch operands (layer, step, table, lengths,
    valid tail slots, query positions), and ``walk`` a word it never reads."""
    s = jax.ShapeDtypeStruct
    b, width, pages, bf16 = 32, 38, 1280, jnp.bfloat16
    pool = (s((LAYERS, pages, HKV, PS, D), I8), s((LAYERS, pages, HKV, PS), F32))
    tail = (s((LAYERS, b, HKV, KT, D), I8), s((LAYERS, b, HKV, KT), F32))
    assert not pa._pages_by_grid(D) and pa._pages_by_grid(LAT_W)
    jaxpr = jax.make_jaxpr(
        lambda *a: pa.quantized_paged_fused_attention(
            *a, sliding_window=4096, interpret=True
        )
    )(
        s((b, 1, HQ, D), bf16), s((b, 1, HKV, D), bf16), s((b, 1, HKV, D), bf16),
        *pool, *pool, *tail, *tail, s((), I32), s((), I32),
        s((b, width), I32), s((b,), I32), s((b,), I32), s((b,), I32),
    )
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    gm = eqn.params["grid_mapping"]
    assert gm.grid == (b,) and gm.num_dynamic_grid_bounds == 0
    assert gm.num_index_operands == 6
    assert [v.aval.shape for v in eqn.invars[:6]] == [
        (1,), (1,), (b, width), (b,), (b,), (b,)]
    whole = [m for m in gm.block_mappings[:gm.num_inputs]
             if m.block_aval.shape == (LAYERS, pages, HKV, PS, D)
             and "any" in str(m.block_aval)]
    assert len(whole) == 2, [str(m.block_aval) for m in gm.block_mappings]
    # nothing but the kernel's scale-row gathers stands beside it: no list
    assert not [e for e in jaxpr.eqns if e.primitive.name == "cumsum"]


@pytest.mark.parametrize("width", [59, SLOTS])
def test_one_plane_tail_flush_at_reason1ks_shapes(chip, width):
    """``paged_tail_flush`` over the latent pool's one stored plane: two
    aliased pool operands where the per-head pool has four."""
    s, b = chip, 32
    _compiles_with_kernel(
        lambda c, cs, tc, tcs, *rows: pa.paged_tail_flush(
            c, cs, None, None, tc, tcs, None, None, *rows, interpret=False
        ),
        s((LAT_LAYERS, LAT_PAGES, 1, PS, LAT_W), I8),
        s((LAT_LAYERS, LAT_PAGES, 1, PS), F32),
        s((LAT_LAYERS, b, 1, KT, LAT_W), I8), s((LAT_LAYERS, b, 1, KT), F32),
        s((b, width), I32), s((b,), I32), s((b,), I32),
    )


@pytest.mark.parametrize("rows", [1, 2, 4])      # one admission; groups of 2, 4
@pytest.mark.parametrize("pool", ["int8", "f32"])
def test_latent_ragged_prefill_kernels_at_moonlights_shapes(chip, pool, rows):
    """A 2048-wide prefill dispatch of 1, 2 and 4 rows, with the block_q the
    kernel picks for 16 heads of 576 (its VMEM estimate rounds the row up to
    640 lanes)."""
    s = chip
    kernel = (
        ra.quantized_latent_ragged_paged_attention if pool == "int8"
        else ra.latent_ragged_paged_attention
    )
    _compiles_with_kernel(
        lambda q, *a: kernel(q, *a, scale=192 ** -0.5, interpret=False),
        s((rows, 2048, LAT_HQ, LAT_W), jnp.bfloat16), *_latent_pool(s, pool),
        s((rows, 59), I32), s((rows,), I32), s((rows,), I32),
    )


def test_flash_attention_kernel(chip):
    s, seq, t = chip, 2048, 4096
    bf16 = jnp.bfloat16
    _compiles_with_kernel(
        lambda *a: fa.flash_attention(*a, interpret=False),
        s((1, seq, HQ, D), bf16), s((1, t, HKV, D), bf16),
        s((1, t, HKV, D), bf16), s((1, seq, t), jnp.bool_),
    )


# keye-vl2-30b-a3b.longdoc: 32 query / 4 kv heads of 128 under a learned
# top-2048 selection, an indexer of 16 heads of 64 over ONE bf16 index key a
# token beside the int8 K and V, 12 layers, 3072 pages of 64, 16 slots, a
# 4096-wide chunk; the table pinned at 182 pages, its cap 256.
KEYE_HKV, KEYE_HI, KEYE_DI, KEYE_TOPK = 4, 16, 64, 2048
KEYE_PAGES, KEYE_LAYERS, KEYE_ROWS = 3072, 12, 16


def _keye_cache(s, rows, width):
    from distributed_llm_inference_tpu.cache.paged import indexed_cache_class

    kv = s((KEYE_LAYERS, KEYE_PAGES, KEYE_HKV, PS, D), I8)
    sc = s((KEYE_LAYERS, KEYE_PAGES, KEYE_HKV, PS), F32)
    return indexed_cache_class(True, KEYE_DI)(
        k_pages=kv, v_pages=kv, ks_pages=sc, vs_pages=sc,
        ik_pages=s((KEYE_LAYERS, KEYE_PAGES, 1, PS, KEYE_DI), jnp.bfloat16),
        page_table=s((rows, width), I32), lengths=s((rows,), I32),
        page_size=PS, use_kernel=True, use_ragged=True,
    )


def _keye_index(s, rows, seq):
    from distributed_llm_inference_tpu.ops.sparse_attention import IndexInputs

    bf16 = jnp.bfloat16
    return lambda q, k, w: IndexInputs(q, k, w, KEYE_TOPK), (
        s((rows, seq, KEYE_HI, KEYE_DI), bf16), s((rows, seq, KEYE_DI), bf16),
        s((rows, seq, KEYE_HI), bf16),
    )


@pytest.mark.parametrize("width", [182, 256])   # the cell's pinned table; the cap
def test_sparse_decode_step_at_keyes_shapes(chip, width):
    """One layer of one step of ``keye-vl2-30b-a3b.longdoc``'s decode scan,
    as the indexed int8 cache runs it: the index key into its tail, the
    scores of the pool's and the tail's index keys, the exact selection, and
    the fused in-place sweep under its mask (``sparse_paged_fused_attention``)
    over the WHOLE 12-layer pool; then the window's flush."""
    from distributed_llm_inference_tpu.ops.rotary import RopeAngles

    s, b, bf16 = chip, KEYE_ROWS, jnp.bfloat16
    make, index = _keye_index(s, b, 1)

    def step(cache, tail, q, k, v, cos, sin, iq, ik, iw, lens, lidx, step):
        out, tail = cache.tail_attend(
            (*cache.tail_big_stacks(), lidx), tail, q, k, v,
            RopeAngles(None, cos, sin), lens, lens * 0, step, lens * 0 + 1,
            None, D ** -0.5, index=make(iq, ik, iw),
        )
        return out, cache.tail_flush(tail, lens * 0 + 1)

    cache = _keye_cache(s, b, width)
    tail = jax.eval_shape(lambda c: c.tail_init(KT), cache)
    tail = jax.tree.map(lambda x: s(x.shape, x.dtype), tail)
    text = jax.jit(step).lower(
        cache, tail, s((b, 1, HQ, D), bf16), s((b, 1, KEYE_HKV, D), bf16),
        s((b, 1, KEYE_HKV, D), bf16), s((b, 1, D), F32), s((b, 1, D), F32),
        *index, s((b,), I32), s((), I32), s((), I32),
    ).compile().as_text()
    assert "sparse_paged_fused_attention" in text
    assert "paged_tail_flush" in text and "index_tail_flush" in text


@pytest.mark.parametrize("width", [182, 256])
def test_sparse_prefill_chunk_at_keyes_shapes(chip, width):
    """One layer of a 4096-wide chunk: the chunk's K, V and index keys into
    the pool, every query's selection over the row's whole table (scored a
    block of queries at a time), and the ragged kernel under the (query,
    key) mask (``sparse_ragged_paged_attention``)."""
    from distributed_llm_inference_tpu.ops.attention import gqa_attention
    from distributed_llm_inference_tpu.ops.rotary import RopeAngles

    s, seq, bf16 = chip, 4096, jnp.bfloat16
    make, index = _keye_index(s, 1, seq)

    def chunk(cache, q, k, v, cos, sin, iq, ik, iw, q_pos, num_new):
        state = tuple(x[3] for x in cache.layer_stacks)
        return cache.attend(
            state, q, k, v, RopeAngles(None, cos, sin), q_pos, num_new, None,
            gqa_attention, D ** -0.5, index=make(iq, ik, iw),
        )

    text = jax.jit(chunk).lower(
        _keye_cache(s, 1, width), s((1, seq, HQ, D), bf16),
        s((1, seq, KEYE_HKV, D), bf16), s((1, seq, KEYE_HKV, D), bf16),
        s((1, seq, D), F32), s((1, seq, D), F32), *index,
        s((1, seq), I32), s((1,), I32),
    ).compile().as_text()
    assert "sparse_ragged_paged_attention" in text


# glm-5.2.codebase: 64 query heads over ONE stored latent of 576, int8, 9
# layers over 3584 pages of which 3 score a selection (an index plane of 3
# rows of 128 bf16) and 6 reuse one, 32 index heads of 128, topk 2048, 16
# rows, a 4096-wide chunk; the table pinned at 227 pages, its cap 256.
GLM_HQ, GLM_LAT, GLM_RANK, GLM_LAYERS, GLM_PAGES, GLM_ROWS = 64, 576, 512, 9, 3584, 16
GLM_HI, GLM_DI, GLM_TOPK = 32, 128, 2048
GLM_SCORING = (True, False, False, False) * 2 + (True,)


def _glm_cache(s, rows, width, seg, sel=None):
    from distributed_llm_inference_tpu.cache.latent import (
        indexed_latent_cache_class,
    )

    return indexed_latent_cache_class(True, GLM_DI, GLM_SCORING)(
        k_pages=s((GLM_LAYERS, GLM_PAGES, 1, PS, GLM_LAT), I8),
        v_pages=s((GLM_LAYERS, 1, 1, 1, 1), F32),
        cs_pages=s((GLM_LAYERS, GLM_PAGES, 1, PS), F32),
        ik_pages=s((3, GLM_PAGES, 1, PS, GLM_DI), jnp.bfloat16),
        page_table=s((rows, width), I32), lengths=s((rows,), I32),
        page_size=PS, use_kernel=True, use_ragged=True, seg=seg, sel=sel,
    )


def _glm_index(s, rows, seq):
    from distributed_llm_inference_tpu.ops.sparse_attention import IndexInputs

    bf16 = jnp.bfloat16
    return lambda q, k, w: IndexInputs(q, k, w, GLM_TOPK), (
        s((rows, seq, GLM_HI, GLM_DI), bf16), s((rows, seq, GLM_DI), bf16),
        s((rows, seq, GLM_HI), bf16),
    )


@pytest.mark.parametrize("width", [227, 256])   # the cell's pinned table; the cap
@pytest.mark.parametrize("kind", ["score", "reuse"])
def test_shared_selection_decode_step_at_glms_shapes(chip, kind, width):
    """One layer of one step of ``glm-5.2.codebase``'s decode scan, as the
    indexed int8 latent cache runs it. A scoring layer (layer 4: row 1 of the
    index plane): the index key into its tail, the scores of the pool's and
    the tail's index keys, the exact selection, and the fused one-plane sweep
    under its mask (``sparse_latent_paged_fused_attention``) over the WHOLE
    9-layer pool, 64 heads against the 576-wide latent; a reusing layer: the
    sweep under the selection the tail state carries. Then the window's
    flush, the index tail's by its own name."""
    s, b, bf16 = chip, GLM_ROWS, jnp.bfloat16
    make, index = _glm_index(s, b, 1)
    cache = _glm_cache(s, b, width, (kind, 1 - 4))

    def step(cache, tail, q, c, iq, ik, iw, lens, lidx, step):
        more = {"index": make(iq, ik, iw)} if kind == "score" else {}
        out, tail = cache.tail_attend(
            (*cache.tail_big_stacks(), lidx), tail, q, c, c, None, lens,
            lens * 0, step, lens * 0 + 1, None, 256 ** -0.5, **more,
        )
        return out, cache.tail_flush(tail, lens * 0 + 1)

    tail = jax.eval_shape(lambda c: c.tail_init(KT), cache)
    tail = jax.tree.map(lambda x: s(x.shape, x.dtype), tail)
    assert [x.shape[0] for x in tail[:3]] == [GLM_LAYERS, GLM_LAYERS, 3]
    text = jax.jit(step).lower(
        cache, tail, s((b, 1, GLM_HQ, GLM_LAT), bf16), s((b, 1, 1, GLM_LAT), bf16),
        *index, s((b,), I32), s((), I32), s((), I32),
    ).compile().as_text()
    assert "sparse_latent_paged_fused_attention" in text
    assert "paged_tail_flush" in text and "latent_index_tail_flush" in text


@pytest.mark.parametrize("width", [227, 256])
@pytest.mark.parametrize("kind", ["score", "reuse"])
def test_shared_selection_prefill_chunk_at_glms_shapes(chip, kind, width):
    """One layer of a 4096-wide chunk: the chunk's latents (and, in a scoring
    layer, index keys) into the pool, every query's selection over the row's
    whole table (scored a block of queries at a time) or the one the layer
    state carries, and the ragged kernel over the one stored plane under the
    (query, key) mask (``sparse_latent_ragged_paged_attention``)."""
    from distributed_llm_inference_tpu.ops.attention import gqa_attention

    s, seq, bf16 = chip, 4096, jnp.bfloat16
    make, index = _glm_index(s, 1, seq)
    cache = _glm_cache(s, 1, width, (kind, 1 - 4), s((1, 1, width, seq, PS), I8))

    def chunk(cache, q, c, iq, ik, iw, q_pos, num_new):
        rows = cache.stack_rows(4)
        state = tuple(x[r] for x, r in zip(cache.layer_stacks, rows))
        more = {"index": make(iq, ik, iw)} if kind == "score" else {}
        return cache.attend(
            state, q, c, c, None, q_pos, num_new, None, gqa_attention,
            256 ** -0.5, **more,
        )

    text = jax.jit(chunk).lower(
        cache, s((1, seq, GLM_HQ, GLM_LAT), bf16), s((1, seq, 1, GLM_LAT), bf16),
        *index, s((1, seq), I32), s((1,), I32),
    ).compile().as_text()
    assert "sparse_latent_ragged_paged_attention" in text


# k-exaone-236b-a23b.mixedlen: 64 query / 8 kv heads of 128, 9 window layers
# (window 128) over a pool of their own of 512 pages beside 3 full layers over
# 4608, int8, 32 rows, a 2048-wide chunk; the table pinned at 227 pages, its
# cap 256. The kernels' bodies are Mistral's at twice its query heads; the
# window pool's calls run under names of their own.
EXA_HQ, EXA_ROWS, EXA_WINDOW = 64, 32, 128
EXA_KINDS = ("window", "window", "window", "full") * 3
EXA_PAGES = {"full": 4608, "window": 512}


def _exaone_cache(s, rows, width):
    from distributed_llm_inference_tpu.cache.paged import two_pool_cache_class

    def planes(layers, pages):
        kv = s((layers, pages, HKV, PS, D), I8)
        sc = s((layers, pages, HKV, PS), F32)
        return kv, kv, sc, sc

    k, v, ks, vs = planes(3, EXA_PAGES["full"])
    wk, wv, wks, wvs = planes(9, EXA_PAGES["window"])
    table = s((rows, width), I32)
    return two_pool_cache_class(True, EXA_KINDS, EXA_WINDOW)(
        k_pages=k, v_pages=v, ks_pages=ks, vs_pages=vs,
        wk_pages=wk, wv_pages=wv, wks_pages=wks, wvs_pages=wvs,
        page_table=table, w_page_table=table, lengths=s((rows,), I32),
        page_size=PS, use_kernel=True, use_ragged=True,
    )


@pytest.mark.parametrize("width", [227, 256])   # the cell's pinned table; the cap
@pytest.mark.parametrize("kind", ["window", "full"])
def test_two_pool_decode_step_at_exaones_shapes(chip, kind, width):
    """One layer of one step of ``k-exaone-236b-a23b.mixedlen``'s decode
    scan in each pool, as the int8 two-pool cache's view runs it: the fused
    in-place sweep over the WHOLE pool of that kind (under the static window,
    or none) and the window's flush, the window pool's under its own
    names."""
    from distributed_llm_inference_tpu.ops.rotary import RopeAngles

    s, b, bf16 = chip, EXA_ROWS, jnp.bfloat16
    window = EXA_WINDOW if kind == "window" else None

    def step(cache, tail, q, k, v, cos, sin, lens, lidx, step):
        view = cache.pool_view(kind)
        out, tail = view.tail_attend(
            (*view.tail_big_stacks(), lidx), tail, q, k, v,
            RopeAngles(None, cos, sin), lens, lens * 0, step, lens * 0 + 1,
            window, D ** -0.5,
        )
        return out, cache.with_pool_view(
            kind, view.tail_flush(tail, lens * 0 + 1), lengths=True
        )

    cache = _exaone_cache(s, b, width)
    tail = jax.eval_shape(lambda c: c.pool_view(kind).tail_init(KT), cache)
    tail = jax.tree.map(lambda x: s(x.shape, x.dtype), tail)
    text = jax.jit(step).lower(
        cache, tail, s((b, 1, EXA_HQ, D), bf16), s((b, 1, HKV, D), bf16),
        s((b, 1, HKV, D), bf16), s((b, 1, D), F32), s((b, 1, D), F32),
        s((b,), I32), s((), I32), s((), I32),
    ).compile().as_text()
    names = (
        ("window_paged_fused_attention", "window_tail_flush") if window
        else ("quantized_paged_fused_attention", "paged_tail_flush")
    )
    for name in names:
        assert name in text, name
    assert ("window_paged_fused_attention" in text) == bool(window)


@pytest.mark.parametrize("rows", [1, 4])        # a chunk or one admission; a group
@pytest.mark.parametrize("kind", ["window", "full"])
def test_two_pool_prefill_chunk_at_exaones_shapes(chip, kind, rows):
    """One layer of a 2048-wide prefill dispatch in each pool: the chunk's K
    and V into that pool by its own table, and the ragged kernel under the
    static window (or none), the window pool's under its own name."""
    from distributed_llm_inference_tpu.ops.attention import gqa_attention
    from distributed_llm_inference_tpu.ops.rotary import RopeAngles

    s, seq, bf16 = chip, 2048, jnp.bfloat16
    window = EXA_WINDOW if kind == "window" else None

    def chunk(cache, q, k, v, cos, sin, q_pos, num_new):
        view = cache.select_rows(jnp.arange(rows)).pool_view(kind)
        state = tuple(x[1] for x in view.layer_stacks)
        return view.attend(
            state, q, k, v, RopeAngles(None, cos, sin), q_pos, num_new,
            window, gqa_attention, D ** -0.5,
        )

    text = jax.jit(chunk).lower(
        _exaone_cache(s, EXA_ROWS, 227), s((rows, seq, EXA_HQ, D), bf16),
        s((rows, seq, HKV, D), bf16), s((rows, seq, HKV, D), bf16),
        s((rows, seq, D), F32), s((rows, seq, D), F32),
        s((rows, seq), I32), s((rows,), I32),
    ).compile().as_text()
    name = (
        "window_ragged_paged_attention" if window
        else "quantized_ragged_paged_attention"
    )
    assert name in text
    assert ("window_ragged_paged_attention" in text) == bool(window)


@pytest.mark.parametrize(
    "hidden,ffn,held,pairs",
    [
        (4096, 14336, 8, 2 * 2048),      # 8 experts, 2 a token, 2048 wide
        (2048, 1408, 64, 6 * 2048),      # 64 of 1408, 6 a token
        (2048, 768, 128, 8 * 4096),      # 128 of 768, 8 a token, 4096 wide
        (6144, 2048, 16, 8 * 2048),      # a share of 16 of 128, 8 a token
        (3584, 1024, 64, 4 * 2048),      # 64 of 1024 under hidden 3584, 4 a token
    ],
    ids=["8x14336", "64x1408", "128x768", "16x2048-share", "64x1024"],
)
def test_grouped_moe_kernel(chip, hidden, ffn, held, pairs):
    """``moe_grouped_matmul`` over int8 expert stacks at the routed cells'
    widths, with the row tile and weight blocks the module picks: gate (or
    up) and down of one layer read out of a two-layer stack (the served
    form), over the worst-case row buffer of a prefill dispatch."""
    from distributed_llm_inference_tpu.ops import moe
    from distributed_llm_inference_tpu.ops.quant import QuantizedTensor

    s = chip
    tiles = -(-(pairs + held * (moe.ROW_TILE - 1)) // moe.ROW_TILE)
    rows = tiles * moe.ROW_TILE
    stack = lambda k, n: QuantizedTensor(
        q=s((2, held, k, n), I8), scale=s((2, held, n), jnp.bfloat16)
    )

    def gate_and_down(x, wg, wd, tile_expert, live, layer):
        mm = lambda x, w: moe.grouped_matmul(
            x, moe.LayerOf(w, layer), tile_expert, live,
            row_tile=moe.ROW_TILE, interpret=False,
        )
        return mm(jax.nn.silu(mm(x, wg)), wd)

    _compiles_with_kernel(
        gate_and_down, s((rows, hidden), jnp.bfloat16), stack(hidden, ffn),
        stack(ffn, hidden), s((tiles,), I32), s((), I32), s((), I32),
    )


@pytest.mark.parametrize(
    "hidden,ffn,held,rows",
    [
        (4096, 14336, 8, 16),       # rag: 16 slots over 8 experts
        (2048, 1408, 64, 32),       # reason1k: 32 slots over 64
        (2048, 768, 128, 16),       # longdoc: 16 slots over 128
        (6144, 2048, 16, 32),       # mixedlen, glm: a share of 16
        (4096, 14336, 8, 128),      # the widest dispatch the rule sends
        (3584, 1024, 64, 32),       # a widened stream's reason1k: 32 slots over 64
    ],
    ids=["8x14336", "64x1408", "128x768", "16x2048-share", "128-rows",
         "64x1024"],
)
def test_live_moe_kernel_at_the_decode_shapes(chip, hidden, ffn, held, rows):
    """The live path's three calls of ``moe_grouped_matmul`` at a decode
    step's shape: one tile of the dispatch's rows an expert, gate and up
    over ONE shared tile of tokens, down over a tile an expert, each
    layer's int8 matrices read out of a two-layer stack."""
    from distributed_llm_inference_tpu.ops import moe
    from distributed_llm_inference_tpu.ops.quant import QuantizedTensor

    s = chip
    stack = lambda k, n: QuantizedTensor(
        q=s((2, held, k, n), I8), scale=s((2, held, n), jnp.bfloat16)
    )

    def gate_up_down(x, wg, wu, wd, order, live, layer):
        mm = lambda x, w: moe.grouped_matmul(
            x, moe.LayerOf(w, layer), order, live, row_tile=rows,
            interpret=False,
        )
        t = mm(x, wg)
        assert t.shape == (held * rows, ffn)
        return mm(jax.nn.silu(t) * mm(x, wu), wd)

    _compiles_with_kernel(
        gate_up_down, s((rows, hidden), jnp.bfloat16), stack(hidden, ffn),
        stack(hidden, ffn), stack(ffn, hidden), s((held,), I32), s((), I32),
        s((), I32),
    )


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, F32], ids=["bf16", "f32"])
def test_retention_decode_kernel_at_brumbys_shapes(chip, q_dtype):
    """``power_retention_decode`` over the WHOLE state stacks of 10 layers x
    16 rows (40 query / 8 kv heads of 128: a head's state 8320 x 128
    float32, 4.26 MB a grid step), the open page's 64 and the tail's 16
    places, walking a list of live rows whose length is data."""
    from distributed_llm_inference_tpu.ops import power_retention as pr

    s, rows, layers, g, n = chip, 16, 10, 5, 80
    width = pr.feature_dim(D)
    _compiles_with_kernel(
        lambda q, st, zs, dec, k, v, w, layer, count, walk:
            pr.power_retention_decode(
                q, st, zs, dec, k, v, w, 1e-6, layer=layer,
                walk=(count, walk), interpret=False,
            ),
        s((rows, HKV, g, D), q_dtype), s((layers, rows, HKV, width, D), F32),
        s((layers, rows, HKV, width), F32), s((rows, HKV), F32),
        s((rows, HKV, n, D), q_dtype), s((rows, HKV, n, D), q_dtype),
        s((rows, HKV, n), F32), s((1,), I32), s((), I32), s((rows,), I32),
    )


def test_retention_fold_kernel_at_brumbys_shapes(chip):
    """``power_retention_fold``: the state and the summed keys of 10 layers
    x 16 rows aliased in place, two pages' 128 positions a row, the rows
    that fold listed as data."""
    from distributed_llm_inference_tpu.ops import power_retention as pr

    s, rows, layers, n = chip, 16, 10, 128
    width = pr.feature_dim(D)
    _compiles_with_kernel(
        lambda st, zs, ref, k, v, g, fold: pr.power_retention_fold(
            st, zs, ref, k, v, g, fold, interpret=False
        ),
        s((layers, rows, HKV, width, D), F32),
        s((layers, rows, HKV, width), F32), s((layers, rows, HKV), F32),
        s((layers, rows, n, HKV, D), jnp.bfloat16),
        s((layers, rows, n, HKV, D), jnp.bfloat16),
        s((layers, rows, n, HKV), F32), s((rows, n), jnp.bool_),
    )


def test_retention_prefill_kernel_at_brumbys_shapes(chip):
    """``power_retention_prefill``: a 4096-wide chunk behind an open page,
    17 steps of 256 positions x 5 query heads a key-value head, the head's
    8320 x 128 state resident in VMEM across them and aliased in place."""
    from distributed_llm_inference_tpu.ops import power_retention as pr

    s, e, g = chip, 4352, 5
    width = pr.feature_dim(D)
    _compiles_with_kernel(
        lambda q, k, v, gs, vq, vk, fold, st, zs, ref:
            pr.power_retention_prefill(
                q, k, v, gs, vq, vk, fold, st, zs, ref, 1e-6, 256,
                interpret=False,
            ),
        s((1, e, HKV, g, D), jnp.bfloat16), s((1, e, HKV, D), jnp.bfloat16),
        s((1, e, HKV, D), jnp.bfloat16), s((1, e, HKV), F32),
        s((1, e), jnp.bool_), s((1, e), jnp.bool_), s((1, e), jnp.bool_),
        s((1, HKV, width, D), F32), s((1, HKV, width), F32), s((1, HKV), F32),
    )


def test_a_fresh_rows_install_stays_sharded_and_in_place_on_a_tp4_mesh(chip):
    """``PagedKVCache.ingest_row`` as the fresh-row prefill of
    ``mistral-7b-bf16-tp4.chat32`` gives it (``engine.py:_prefill_row_fresh``):
    a 640-page bf16 pool sharded by kv head over the four chips of a v5e
    2x2, a 38-slot table, a 2048-wide piece's K/V sharded as the pool. The
    owned pages are written in place by a loop of dynamic-update-slices:
    no collective, no copy of a pool plane (the per-position scatter's
    relayout, PERF.md section 5, PR 55), no array as wide as the table's
    span."""
    import re

    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_llm_inference_tpu.cache.paged import PagedKVCache
    from distributed_llm_inference_tpu.parallel import cache_pspecs

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 4, 1, 1),
                ("dp", "pp", "tp", "sp", "ep"))
    on = lambda spec: NamedSharding(mesh, spec)
    rows, slots, pages, width = 32, 38, 640, 2048
    cache = jax.eval_shape(lambda: PagedKVCache.create(
        LAYERS, rows, pages, PS, slots, HKV, D, jnp.bfloat16))
    cache = jax.tree.map(
        lambda x, spec: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on(spec)),
        cache, cache_pspecs(cache),
    )
    kv = jax.ShapeDtypeStruct(
        (LAYERS, 1, width, HKV, D), jnp.bfloat16,
        sharding=on(P(None, None, None, "tp", None)),
    )
    scalar = jax.ShapeDtypeStruct((), I32, sharding=on(P()))

    def install(cache, k, v, row, n_valid):
        sub = cache.select_row(row).ingest_row(k, v, n_valid)
        return cache.merge_row(sub, row)

    with mesh:
        text = jax.jit(install, donate_argnums=(0,)).lower(
            cache, kv, kv, scalar, scalar).compile().as_text()
    results = [ln.split(" = ", 1)[1] for ln in text.splitlines() if " = " in ln]
    assert not [r for r in results if re.match(r"\S+ (all-gather|all-reduce|all-to-all|collective-permute)", r)]
    plane = rf"bf16\[({LAYERS},)?{pages},{HKV // 4},{PS},{D}\]"
    assert not [r for r in results if re.match(plane + r"\S* copy\(", r)]
    assert not [r for r in results if re.match(rf"\S*\[[0-9,]*\b{slots * PS}\b", r)]
    assert [r for r in results if re.match(plane + r"\S* dynamic-update-slice\(", r)]
    assert "input_output_alias" in text and " while(" in text


# ouro-2.6b.mathchat: 48 layers that every token crosses four times over a
# cache of 4 x 48 = 192 layers: 16 query and 16 key-value heads of 128 (a
# group of ONE query a key-value head), bf16 weights, an int8 pool of 160
# pages of 64, 16 rows, a 16-slot table, one 512-wide pad width.
OURO_SLOTS, OURO_PAD = 16, 512


def _ouro(s):
    import json
    import pathlib

    from benchmark import server
    from benchmark.weights import ouro_looped_gqa as maker
    from distributed_llm_inference_tpu.cache.paged import QuantizedPagedKVCache
    from distributed_llm_inference_tpu.config import ModelConfig

    conf = json.loads(
        (pathlib.Path(server.REPO) / "benchmark/configs/ouro-2.6b.json").read_text()
    )
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    abstract = lambda tree: jax.tree.map(lambda x: s(x.shape, x.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda: maker.make(cfg, 0, jnp.bfloat16, None)
    ))
    serve = conf["serve"]
    assert serve["cache"]["kv_quant"] == "int8" and serve["weights"] == "bf16"
    cache = abstract(jax.eval_shape(lambda: QuantizedPagedKVCache.create(
        cfg.cache_layers, serve["engine"]["max_batch_size"],
        serve["cache"]["num_pages"], serve["cache"]["page_size"], OURO_SLOTS,
        cfg.num_kv_heads, cfg.head_dim, use_kernel=True, use_ragged=True,
    )))
    return cfg, params, cache


# mistral-7b.chat / .reason: 32 layers, int8 weights, the cell's int8 pool of
# 1280 pages, 32 rows, a 64-slot table, the 2048-wide pad width.
def _mistral(s):
    import json
    import pathlib

    from benchmark import server
    from benchmark.weights import dense_gqa as maker
    from distributed_llm_inference_tpu.cache.paged import QuantizedPagedKVCache
    from distributed_llm_inference_tpu.config import ModelConfig

    conf = json.loads(
        (pathlib.Path(server.REPO) / "benchmark/configs/mistral-7b.json").read_text()
    )
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    abstract = lambda tree: jax.tree.map(lambda x: s(x.shape, x.dtype), tree)
    serve = conf["serve"]
    assert serve["cache"]["kv_quant"] == "int8" and serve["weights"] == "int8"
    params = abstract(jax.eval_shape(
        lambda: maker.make(cfg, 0, jnp.bfloat16, "int8")
    ))
    cache = abstract(jax.eval_shape(lambda: QuantizedPagedKVCache.create(
        cfg.num_layers, serve["engine"]["max_batch_size"],
        serve["cache"]["num_pages"], serve["cache"]["page_size"], SLOTS,
        cfg.num_kv_heads, cfg.head_dim, use_kernel=True, use_ragged=True,
    )))
    return cfg, params, cache


#: arguments + temporaries of Ouro's 512-wide prefill on the parent of PR 58
#: (commit 738f3c5; this file's compile against that tree): 13.72 GB of
#: arguments and 2.02 GB of temporaries, the pool's planes on their way
#: through the position scatter's layout
OURO_PREFILL_HELD_BEFORE = 13_724_569_600 + 2_017_048_064


@pytest.mark.parametrize("served,width", [("ouro", OURO_PAD), ("mistral", 2048)])
def test_a_prefill_over_the_int8_pool_has_no_layers_plane_as_a_result(
    chip, monkeypatch, served, width
):
    """``engine.py:_prefill_row`` at the served shapes of
    ``ouro-2.6b.mathchat`` (the looped scan, 192 cache layers of 160 pages,
    16 kv heads) and of ``mistral-7b.chat`` / ``.reason`` (32 layers of 1280
    pages, 8 kv heads, int8 weights): the cache writes the piece by whole
    pages and the ragged kernel reads at (layer, page) of the carried stacks
    (``QuantizedPagedKVCache.ragged_reads_whole_stacks``), so NO operation
    of the compiled program has a layer's K or V plane, or a layer's scale
    plane, as its result: not the slice out of the carry, not the relayout
    XLA's scatter wants, not the relayout back, not a copy on the way to the
    write-back (four of them a plane a layer before: 16 x 21 MB x 192 a
    dispatch at Ouro's pool, 16 x 84 MB x 32 at Mistral's, whatever the
    piece's width). The pool is still the donated argument, aliased to the
    result; Ouro's arguments and temporaries stand under the parent's."""
    import re

    from distributed_llm_inference_tpu.models import llama

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = chip
    cfg, params, cache = (_ouro if served == "ouro" else _mistral)(s)
    layers, pages, hkv = cache.k_pages.shape[:3]

    def prefill(params, tokens, cache, row, n_valid):
        sub = cache.select_row(row)
        logits, sub = llama.model_apply(
            cfg, params, tokens, sub, n_valid[None], head="last"
        )
        return logits, cache.merge_row(sub, row)

    compiled = jax.jit(prefill, donate_argnums=(2,)).lower(
        params, s((1, width), I32), cache, s((), I32), s((), I32)
    ).compile()
    text = compiled.as_text()
    assert " while(" in text and "input_output_alias" in text
    for kernel in ("quantized_ragged_paged_attention", "paged_piece_write"):
        assert kernel in text, kernel
    results = [ln.split(" = ", 1) for ln in text.splitlines() if " = " in ln]
    planes = (
        rf"s8\[(1,)?{pages},{hkv},{PS},{D}\]", rf"f32\[(1,)?{pages},{hkv},{PS}\]",
    )
    made = r"\S* (copy|dynamic-slice|dynamic-update-slice|transpose|fusion|scatter)\("
    assert not [
        name + " = " + r[:90] for name, r in results for plane in planes
        if re.match(plane + made, r)
    ]
    # every stack is carried whole through the layers' loop and handed to
    # the kernels at (layer, page)
    stack = rf"s8\[{layers},{pages},{hkv},{PS},{D}\]"
    assert [r for _, r in results if re.match(r"\(?.*" + stack + r".* while\(", r)]
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.75 * 2 ** 30, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    if served == "ouro":
        # what is left of the 2.0 GB: the two float32 scale stacks in the
        # kernels' row-major layout, once a dispatch (0.5 GB)
        assert held < OURO_PREFILL_HELD_BEFORE - 1.2e9, held
    print(served, "arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)


#: temporaries of the fused 16-step decode scan on the parent of PR 61 (commit
#: 6669722; this file's compile against that tree), bytes. PR 61's own read
#: LESS, 2_014_785_536 and 647_214_592: the one joined plane (128 lanes, no
#: padding) takes the place of the two stored planes' row-major forms (64
#: offsets minor, padded to the 128 lanes) that the gather's layer slices read
DECODE_SCAN_TEMP_BEFORE = {"ouro": 2_115_158_016, "mistral": 815_616_000}


def _decode_scan(cfg, exit_laps):
    """The fused 16-step decode scan under a greedy step function."""
    from distributed_llm_inference_tpu.models import llama

    def decode(params, tokens, cache, active):
        return llama.multi_decode_apply(
            cfg, params, tokens, cache, KT,
            lambda i, logits, alive: (
                jnp.argmax(logits, -1).astype(I32), alive.astype(I32), alive,
                jnp.argmax(logits, -1).astype(I32),
            ),
            active, active.astype(I32), exit_laps=exit_laps,
        )

    return decode


def _joined_plane_bytes(cache):
    layers, pages, hkv, ps = cache.ks_pages.shape
    return layers * pages * hkv * 2 * ps * 4


def test_the_decode_scan_at_mistrals_served_shapes(chip, monkeypatch):
    """``mistral-7b.reason`` / ``.chat``: the fused 16-step decode scan of 32
    rows over the cell's pool (32 layers of 1280 pages, int8 weights) still
    compiles and fits, holds no gather of a scale plane's rows (the sweep
    copies them by the live page out of the plane joined once, outside both
    scans), and its temporaries are no more than the parent's plus that
    joined plane."""
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = chip
    cfg, params, cache = _mistral(s)
    rows, slots = cache.page_table.shape
    compiled = jax.jit(_decode_scan(cfg, False), donate_argnums=(2,)).lower(
        params, s((rows, 1), I32), cache, s((rows,), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    for name in ("quantized_paged_fused_attention", "paged_tail_flush"):
        assert name in text, name
    pages, hkv = cache.k_pages.shape[1:3]
    assert f"f32[{rows * slots},{hkv},{PS}]" not in text      # the gather
    assert not re.search(rf"= f32\[{pages},{hkv},{PS}\]\S* fusion\(", text)  # its slice
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.75 * 2 ** 30, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    assert mem.temp_size_in_bytes <= (
        DECODE_SCAN_TEMP_BEFORE["mistral"] + _joined_plane_bytes(cache)
    ), mem.temp_size_in_bytes
    print("mistral decode-scan arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)


@pytest.mark.parametrize("program", ["prefill", "decode-scan"])
def test_the_looped_programs_at_ouros_published_widths(chip, monkeypatch, program):
    """The looped prefill (one row's 512-wide piece through its page table,
    head last) and the fused 16-step decode scan of 16 rows, WHOLE, at the
    published widths over the cell's pool: the lap scan around the layer
    scan, the ragged prefill kernel and the in-place decode sweep at a group
    of one query a key-value head over 192 cache layers, the tail's flush.
    Bytes and temporaries are known before the first chip call: arguments
    and temporaries fit the chip's 17.18 GB."""
    from distributed_llm_inference_tpu.models import llama

    # the kernels ask the backend whether to interpret: the described chip's
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = chip
    cfg, params, cache = _ouro(s)
    rows, pages = cache.page_table.shape[0], cache.k_pages.shape[1]
    assert cache.k_pages.shape == (192, pages, 16, PS, 128)

    def prefill(params, tokens, cache, row, n_valid):
        sub = cache.select_row(row)
        logits, sub = llama.model_apply(
            cfg, params, tokens, sub, n_valid[None], head="last"
        )
        return logits, cache.merge_row(sub, row)

    decode = _decode_scan(cfg, True)

    if program == "prefill":
        lowered = jax.jit(prefill, donate_argnums=(2,)).lower(
            params, s((1, OURO_PAD), I32), cache, s((), I32), s((), I32)
        )
        kernels = ("quantized_ragged_paged_attention",)
    else:
        lowered = jax.jit(decode, donate_argnums=(2,)).lower(
            params, s((rows, 1), I32), cache, s((rows,), jnp.bool_)
        )
        kernels = ("quantized_paged_fused_attention", "paged_tail_flush")
    compiled = lowered.compile()
    text = compiled.as_text()
    for name in kernels + ("loop_lap", "loop_exit"):
        assert name in text, name
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    # 5.34 GB of bf16 weights and the pool (8.3 GB at 160 pages) are
    # arguments, the pool aliased to the result; the temporaries fit what
    # is left of the 16.9 GB (15.75 GiB) the compiler may use: 0.50 GB for
    # the prefill since PR 58 (the pool's two float32 scale stacks, which
    # lie in the device's compact layout between dispatches, in the kernels'
    # row-major one: 64 offsets minor, padded to the 128 lanes; 2.0 GB
    # before, the planes on their way through the scatter's layout) and 2.1
    # GB for the scan
    assert mem.argument_size_in_bytes > 5.3e9 + 50e6 * pages
    assert held < 15.75 * 2 ** 30, (mem.argument_size_in_bytes, mem.temp_size_in_bytes)
    if program == "decode-scan":
        # since PR 61 the scan holds K's and V's scale rows joined in one
        # plane while it runs, and no more than that over the parent's
        assert mem.temp_size_in_bytes <= (
            DECODE_SCAN_TEMP_BEFORE["ouro"] + _joined_plane_bytes(cache)
        ), mem.temp_size_in_bytes
    print(program, "arguments", mem.argument_size_in_bytes,
          "temporaries", mem.temp_size_in_bytes)
