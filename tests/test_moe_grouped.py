"""The dropless grouped dispatch of ``ops/moe.py``: prefill-scale dispatches
compute each token's own experts (``moe_mlp_grouped`` over the Pallas
kernel ``moe_grouped_matmul``, interpreted here), a decode-shaped dispatch
reads the experts its live rows picked (``moe_mlp_live``, the same kernel
with one tile an expert), what lies between and a mesh keep dense-combine,
and ``dispatch_path`` picks between them from the shape.

Toy widths throughout (H, F <= 128, 4-8 experts, a row tile of 8): what the
chip runs at the cells' widths is ``tests/test_chip_compile.py``'s and the
benchmark's to say."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from distributed_llm_inference_tpu.config import (
    CacheConfig,
    EngineConfig,
    MeshConfig,
    ModelConfig,
)
from distributed_llm_inference_tpu.ops import moe
from distributed_llm_inference_tpu.ops.quant import quantize_params
from distributed_llm_inference_tpu.parallel import build_mesh

H, F, E, K, TILE = 64, 32, 8, 2, 8


def config(**over) -> ModelConfig:
    kw = dict(
        vocab_size=128, hidden_size=H, intermediate_size=F,
        moe_intermediate_size=F, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=8, max_position_embeddings=256, num_experts=E,
        num_experts_per_tok=K, family="mixtral",
    )
    kw.update(over)
    return ModelConfig(**kw)


SOFTMAX = config()
SIGMOID = config(
    moe_scoring="sigmoid", moe_select_bias=True, moe_routed_scale=2.5
)


def layer(cfg: ModelConfig, seed=0, dtype=jnp.float32, shared=0):
    """One routed layer's leaves: the held stack, a bias where the rule
    selects by one, shared experts where asked."""
    held = cfg.num_held_experts
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    normal = lambda *shape: (
        jax.random.normal(next(keys), shape, jnp.float32) * shape[-2] ** -0.5
    ).astype(dtype)
    p = {
        "router": normal(H, cfg.num_experts),
        "we_g": normal(held, H, F), "we_u": normal(held, H, F),
        "we_d": normal(held, F, H),
    }
    if cfg.moe_select_bias:
        p["router_bias"] = jax.random.normal(
            next(keys), (cfg.num_experts,), jnp.float32
        ) * 0.1
    if shared:
        p.update(ws_g=normal(H, F * shared), ws_u=normal(H, F * shared),
                 ws_d=normal(F * shared, H))
    return p


def tokens(rows, width, seed=1, dtype=jnp.float32):
    return jax.random.normal(
        jax.random.PRNGKey(seed), (rows, width, H), jnp.float32
    ).astype(dtype)


@pytest.fixture
def kernel(monkeypatch):
    """The Pallas kernel itself, interpreted, wherever a path calls
    ``grouped_matmul`` (off a TPU the paths run its plain-XLA reference)."""
    monkeypatch.setattr(
        moe, "grouped_matmul",
        functools.partial(moe.grouped_matmul, interpret=True),
    )


@pytest.fixture
def tile8(monkeypatch):
    """The rule and the kernel at a row tile of 8: a dispatch of 32 tokens
    fills 8 experts' tiles, as 2048 fill them at 128."""
    monkeypatch.setattr(moe, "ROW_TILE", TILE)


# -- grouped against dense-combine -------------------------------------------

# What a parity case runs against dense-combine. The first three hold both
# sides to one layer and one input: float32 to rounding, bf16 activations
# and int8 stacks (the served form) within four of bf16's steps at the
# results' scale (the kernel scales its f32 sum before it rounds, the einsum
# after). ``quantized`` holds the int8 stacks to the FLOAT layer they were
# made from (what quantising the experts costs a routed sum), ``padded``
# puts junk where ``valid`` is false and holds the real tokens' rows to the
# clean input's.
STACKS = ["float32", "bfloat16", "int8", "quantized", "padded"]


def parity(monkeypatch, cfg, shared, stack, rows, seq, valid):
    """``moe_mlp`` by the rule and dense-combine's answer, both zeroed
    where ``valid`` is false, compared as ``stack`` says."""
    act = jnp.bfloat16 if stack in ("bfloat16", "int8") else jnp.float32
    want_p = p = layer(cfg, dtype=act, shared=shared)
    want_x = x = tokens(rows, seq, dtype=act)
    if stack in ("int8", "quantized"):
        p = quantize_params(p, scale_dtype=jnp.float32)
        assert type(p["we_g"]).__name__ == "QuantizedTensor"
        if stack == "int8":
            want_p = p
    if stack == "padded":
        x = jnp.where(valid[..., None], x, x[:1, :1] * 50.0)
    got = moe.moe_mlp(cfg, p, x, valid)
    monkeypatch.setattr(moe, "dispatch_path", lambda *a, **k: "dense")
    want = moe.moe_mlp(cfg, want_p, want_x, valid)
    keep = np.asarray(valid)[..., None]
    a = np.where(keep, np.asarray(got, np.float32), 0)
    b = np.where(keep, np.asarray(want, np.float32), 0)
    assert got.dtype == want.dtype == act
    if stack == "quantized":
        cos = (a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b))
        assert 0.98 < cos < 1.0, cos
    else:
        tol = 1e-5 if act == jnp.float32 else 2 ** -5 * np.abs(b).max()
        np.testing.assert_allclose(a, b, atol=tol, rtol=0)
    return p, x


@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize("shared", [0, 1], ids=["routed_only", "shared_expert"])
@pytest.mark.parametrize("cfg", [SOFTMAX, SIGMOID], ids=["softmax", "sigmoid_bias"])
def test_grouped_equals_dense_combine(kernel, tile8, monkeypatch, cfg, shared, stack):
    """``moe_mlp`` at a prefill shape (grouped by the rule) against
    dense-combine, a case of ``STACKS``."""
    valid = jnp.arange(24)[None, :] < jnp.array([[13], [24]])
    assert moe.dispatch_path(cfg, 2, 24) == "grouped"
    parity(monkeypatch, cfg, shared, stack, 2, 24, valid)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (2048, 2048)])
def test_grouped_matmul_walks_blocks_of_the_weights(blocks):
    """The kernel alone against a per-row einsum, at weight blocks smaller
    than the matrix (2 x 2 of them), in between and wider: the live tiles'
    rows do not depend on the blocking, each is its own expert's."""
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 256, 256), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (6 * TILE, 256), jnp.float32)
    tile_expert = jnp.array([0, 0, 2, 3, 3, 3], jnp.int32)
    got = moe.grouped_matmul(
        x, w, tile_expert, jnp.int32(4), row_tile=TILE, blocks=blocks,
        interpret=True,
    )
    want = jnp.einsum(
        "rk,rkn->rn", x, w[jnp.repeat(tile_expert, TILE)],
        precision="highest",
    )
    live = 4 * TILE
    np.testing.assert_allclose(
        np.asarray(got[:live]), np.asarray(want[:live]), atol=1e-3, rtol=1e-5
    )


@pytest.mark.parametrize("stack", ["float32", "int8"])
@pytest.mark.parametrize("rows", ["own_rows", "shared_rows"])
def test_the_xla_reference_is_the_kernels_product(rows, stack):
    """Off a TPU the paths run ``grouped_matmul``'s plain-XLA reference: the
    interpreted kernel's result on the live tiles, each tile its expert's
    matrix of the view's layer, for a tile of rows an expert and for one
    tile every expert shares (the live path's gate and up); the tiles the
    kernel leaves unwritten are NaN there."""
    act = jnp.float32 if stack == "float32" else jnp.bfloat16
    w = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 64, 32), act)
    if stack == "int8":
        w = quantize_params({"we_g": w}, scale_dtype=jnp.float32)["we_g"]
    tiles = 5
    x = jax.random.normal(
        jax.random.PRNGKey(1),
        ((tiles if rows == "own_rows" else 1) * 16, 64), act,
    )
    order = jnp.array([3, 1, 0, 2, 2], jnp.int32)
    call = functools.partial(
        moe.grouped_matmul, x, moe.LayerOf(w, jnp.int32(1)), order,
        jnp.int32(3), row_tile=16,
    )
    ref, ker = np.asarray(call(), np.float32), np.asarray(
        call(interpret=True), np.float32
    )
    assert ref.shape == ker.shape == (tiles * 16, 32)
    np.testing.assert_allclose(ref[:48], ker[:48], atol=1e-5, rtol=1e-5)
    assert np.isnan(ref[48:]).all()
    plain = w.q.astype(jnp.float32) * w.scale[..., None, :] if stack == "int8" else w
    want = jnp.einsum(
        "trk,tkn->trn",
        jnp.broadcast_to(x.reshape(-1, 16, 64), (tiles, 16, 64)).astype(jnp.float32),
        plain[1][order].astype(jnp.float32), precision="highest",
    ).reshape(tiles * 16, 32)
    tol = 1e-4 if stack == "float32" else 2 ** -6 * np.abs(want).max()
    np.testing.assert_allclose(ker[:48], np.asarray(want)[:48], atol=tol, rtol=0)


# -- dropless ----------------------------------------------------------------


def test_one_expert_takes_every_token_and_nothing_is_dropped(tile8):
    """A routing that sends every token's first pick to expert 5 and its
    second to expert 2: 48 rows each, six tiles for either and none for the
    six others. Every pair is computed."""
    cfg = SOFTMAX
    p = layer(cfg)
    router = np.zeros((H, E), np.float32)
    router[0, 5], router[0, 2] = 4.0, 2.0
    p["router"] = jnp.asarray(router)
    x = tokens(1, 48).at[..., 0].set(3.0)
    _, picks = moe.route(cfg, x.reshape(-1, H), p["router"])
    assert np.asarray(picks).tolist() == [[5, 2]] * 48
    got = moe.moe_mlp_grouped(cfg, p, x)
    want = moe._dense_combine(cfg, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("cfg", [SOFTMAX, SIGMOID], ids=["softmax", "sigmoid_bias"])
def test_a_tokens_rows_do_not_depend_on_chunks_or_neighbours(tile8, cfg):
    """The same 64 tokens as one dispatch, as two chunks of 32, and beside
    another prompt's row: the same result rows."""
    p = layer(cfg)
    x = tokens(1, 64)
    other = tokens(1, 64, seed=7) * 3.0
    for shape in ((1, 64), (1, 32), (2, 64)):
        assert moe.dispatch_path(cfg, *shape) == "grouped"
    whole = np.asarray(moe.moe_mlp(cfg, p, x))
    chunks = np.concatenate(
        [np.asarray(moe.moe_mlp(cfg, p, x[:, i:i + 32])) for i in (0, 32)], 1
    )
    beside = np.asarray(moe.moe_mlp(cfg, p, jnp.concatenate([other, x])))[1:]
    np.testing.assert_allclose(chunks, whole, atol=1e-6, rtol=0)
    np.testing.assert_allclose(beside, whole, atol=1e-6, rtol=0)


# -- what is parked ----------------------------------------------------------


def test_padding_is_parked_and_contributes_zero(tile8):
    """Bucket padding takes no row of an expert: its routed result is zero,
    and the real tokens' rows are what they are without the junk."""
    cfg = SIGMOID
    p = layer(cfg)
    x = tokens(1, 40)
    valid = (jnp.arange(40) < 23)[None, :]
    junk = jnp.where(valid[..., None], x, x[:, :1] * 50.0)
    got = np.asarray(moe.moe_mlp_grouped(cfg, p, junk, valid))
    clean = np.asarray(moe.moe_mlp_grouped(cfg, p, x[:, :23]))
    assert not got[:, 23:].any()
    np.testing.assert_allclose(got[:, :23], clean, atol=1e-6, rtol=0)
    # the parked pairs sit behind every group: the kernel's live tiles hold
    # the valid tokens' pairs and no others
    _, pair_e = moe._routed_pairs(cfg, p, junk.reshape(-1, H), valid)
    assert int((np.asarray(pair_e) < E).sum()) == 23 * K
    assert (np.asarray(pair_e).reshape(40, K)[23:] == E).all()


@pytest.mark.parametrize("shares", [2, 4])
def test_picks_of_another_share_are_parked(tile8, shares):
    """A program that holds a share computes the picks that fall in it and
    parks the rest: the shares' results sum to the whole model's, and each
    equals dense-combine over its own held columns."""
    whole_cfg = SIGMOID
    whole = layer(whole_cfg)
    x = tokens(1, 64)
    want = np.asarray(moe._dense_combine(whole_cfg, whole, x))
    total = np.zeros_like(want)
    held = E // shares
    for index in range(shares):
        cfg = dataclasses.replace(
            whole_cfg, expert_shares=shares, expert_share_index=index
        )
        assert cfg.num_held_experts == held
        p = dict(whole)
        for name in ("we_g", "we_u", "we_d"):
            p[name] = whole[name][index * held:(index + 1) * held]
        assert moe.dispatch_path(cfg, 1, 64) == "grouped"
        got = np.asarray(moe.moe_mlp(cfg, p, x))
        np.testing.assert_allclose(
            got, np.asarray(moe._dense_combine(cfg, p, x)), atol=1e-5
        )
        _, pair_e = moe._routed_pairs(cfg, p, x.reshape(-1, H), None)
        _, picks = moe.route(cfg, x.reshape(-1, H), p["router"], p["router_bias"])
        here = (np.asarray(picks) // held == index).reshape(-1)
        assert ((np.asarray(pair_e) < held) == here).all()
        total += got
    np.testing.assert_allclose(total, want, atol=1e-5)


def test_a_dispatch_whose_every_pair_is_parked_is_zero(tile8):
    cfg = SOFTMAX
    got = moe.moe_mlp_grouped(
        cfg, layer(cfg), tokens(1, 32), jnp.zeros((1, 32), bool)
    )
    assert not np.asarray(got).any()


# -- the live path: a decode-shaped dispatch ---------------------------------

# (rows, tokens a row, rows that hold a request): decode steps of 4, 16 and
# 32 slots and a verify step of 3 tokens a row
LIVE_SHAPES = [(4, 1, 3), (16, 1, 4), (32, 1, 27), (16, 3, 5)]


def dead_rows(rows, seq, alive):
    """``valid`` of a dispatch whose first ``alive`` rows hold a request,
    the last of them a token short where a row has several."""
    num_new = jnp.where(jnp.arange(rows) < alive, seq, 0)
    num_new = num_new.at[alive - 1].set(max(seq - 1, 1))
    return jnp.arange(seq)[None, :] < num_new[:, None]


def live_calls(monkeypatch):
    """Spy on the kernel's calls: ``(tile_expert, live_tiles, row_tile)``."""
    calls, real = [], moe.grouped_matmul

    def spy(x, w, tile_expert, live_tiles, row_tile, **kw):
        calls.append((np.asarray(tile_expert), int(live_tiles), row_tile))
        return real(x, w, tile_expert, live_tiles, row_tile, **kw)

    monkeypatch.setattr(moe, "grouped_matmul", spy)
    return calls


@pytest.mark.parametrize("shape", LIVE_SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("stack", STACKS)
@pytest.mark.parametrize("shared", [0, 1], ids=["routed_only", "shared_expert"])
@pytest.mark.parametrize("cfg", [SOFTMAX, SIGMOID], ids=["softmax", "sigmoid_bias"])
def test_live_equals_dense_combine(request, monkeypatch, cfg, shared, stack, shape):
    """``moe_mlp`` at a decode or verify shape (live by the rule) with dead
    rows, against dense-combine, a case of ``STACKS`` within the grouped
    path's tolerance; the kernel ran one tile an expert over the live
    experts alone: ``live_tiles`` is the count of distinct experts the
    valid tokens picked, and they lead the order. The 16-slot decode step
    runs the interpreted kernel, the other shapes its XLA reference."""
    rows, seq, alive = shape
    if shape == (16, 1, 4):
        request.getfixturevalue("kernel")
    valid = dead_rows(rows, seq, alive)
    assert moe.dispatch_path(cfg, rows, seq) == "live"
    calls = live_calls(monkeypatch)
    p, x = parity(monkeypatch, cfg, shared, stack, rows, seq, valid)
    _, picks = moe.route(cfg, x, p["router"], p.get("router_bias"))
    picked = np.unique(np.asarray(picks)[np.asarray(valid)])
    assert len(calls) == 3
    for order, live, row_tile in calls:
        assert row_tile == -(-rows * seq // 16) * 16
        assert live == len(picked)
        assert live < E or alive * seq * K > E      # few rows: few experts
        assert sorted(order[:live]) == picked.tolist()
        assert sorted(order) == list(range(E))


@pytest.mark.parametrize("shares", [2, 4])
def test_the_live_path_parks_picks_of_another_share(monkeypatch, shares):
    """A program that holds a share reads the held experts its live rows
    picked: the shares' results sum to the whole model's, each equals
    dense-combine over its own columns, and no pick of another share makes
    an expert live."""
    whole_cfg = SIGMOID
    whole = layer(whole_cfg)
    x = tokens(16, 1)
    valid = dead_rows(16, 1, 2)
    keep = np.asarray(valid)[..., None]
    want = np.where(keep, np.asarray(moe._dense_combine(whole_cfg, whole, x)), 0)
    _, picks = moe.route(whole_cfg, x, whole["router"], whole["router_bias"])
    picked = np.unique(np.asarray(picks)[np.asarray(valid)])
    calls = live_calls(monkeypatch)
    total = np.zeros_like(want)
    held = E // shares
    for index in range(shares):
        cfg = dataclasses.replace(
            whole_cfg, expert_shares=shares, expert_share_index=index
        )
        p = dict(whole)
        for name in ("we_g", "we_u", "we_d"):
            p[name] = whole[name][index * held:(index + 1) * held]
        assert moe.dispatch_path(cfg, 16, 1) == "live"
        got = np.asarray(moe.moe_mlp(cfg, p, x, valid))
        np.testing.assert_allclose(
            np.where(keep, got, 0),
            np.where(keep, np.asarray(moe._dense_combine(cfg, p, x)), 0),
            atol=1e-5,
        )
        here = picked[picked // held == index] - index * held
        order, live, _ = calls[-1]
        assert live == len(here) and sorted(order[:live]) == here.tolist()
        total += got
    np.testing.assert_allclose(total, want, atol=1e-5)


@pytest.mark.parametrize("stack", ["float32", "int8"])
def test_a_live_dispatch_whose_every_row_is_dead_is_zero(monkeypatch, stack):
    """No valid row: no expert is live, the kernel's calls fetch and
    compute nothing, and the routed sum is zero, not the unwritten tiles'
    content."""
    cfg = SOFTMAX
    p = layer(cfg, dtype=jnp.bfloat16)
    if stack == "int8":
        p = quantize_params(p, scale_dtype=jnp.float32)
    calls = live_calls(monkeypatch)
    x = tokens(16, 1, dtype=jnp.bfloat16)
    for junk in (x, jnp.full_like(x, jnp.nan)):
        got = np.asarray(
            moe.moe_mlp(cfg, p, junk, jnp.zeros((16, 1), bool)), np.float32
        )
        assert not np.isnan(got).any() and not got.any()
    assert [live for _, live, _ in calls] == [0] * 6


def test_a_dead_rows_garbage_never_reaches_a_live_row(monkeypatch):
    """Dead rows poisoned with ``inf`` (``nan`` in the router's scores and in
    any product): the live rows' results are what they are beside clean dead
    rows, the dead rows' routed sum is zero, and the poison makes no expert
    live."""
    cfg = SIGMOID
    p = layer(cfg)
    x = tokens(16, 1)
    valid = dead_rows(16, 1, 4)
    keep = np.asarray(valid)[..., None]
    poisoned = jnp.where(keep, x, jnp.inf)
    calls = live_calls(monkeypatch)
    clean = np.asarray(moe.moe_mlp(cfg, p, x, valid))
    got = np.asarray(moe.moe_mlp(cfg, p, poisoned, valid))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    assert not got[4:].any() and got[:4].any()
    assert calls[0][1] == calls[3][1] <= 4 * K
    np.testing.assert_array_equal(calls[0][0], calls[3][0])


# -- the rule ----------------------------------------------------------------

# the routed configurations the benchmark serves, as (router width, picks,
# shares, prefill width): what the rule reads of them
MIXTRAL = (8, 2, 1, 2048)
MOONLIGHT = (64, 6, 1, 2048)
KEYE = (128, 8, 1, 4096)
EXAONE = (128, 8, 8, 2048)
GLM = (256, 8, 16, 4096)
ROUTED = {"mixtral": MIXTRAL, "moonlight": MOONLIGHT, "keye": KEYE,
          "exaone": EXAONE, "glm": GLM}


def routed(shape) -> ModelConfig:
    experts, k, shares, _ = shape
    return config(
        num_experts=experts, num_experts_per_tok=k, expert_shares=shares
    )


@pytest.mark.parametrize("name", sorted(ROUTED))
@pytest.mark.parametrize("case,want", [
    ("prefill", "grouped"), ("two_row_prefill", "grouped"),
    ("decode_4", "live"), ("decode_32", "live"), ("verify_32x5", "dense"),
    ("bucket_64", "live"), ("prefill_under_a_mesh", "dense"),
    ("decode_16", "live"), ("verify_16x3", "live"), ("bucket_256", "dense"),
    ("decode_32_under_a_mesh", "dense"),
])
def test_the_rules_table(name, case, want):
    """Static, from the shape and the config alone, at the kernel's own
    128-row tile: the five cells' prefill shapes grouped; their decode
    steps (16 or 32 slots), a verify step of a few tokens a row and a
    bucket that fits one tile live; what lies between the two (a wide
    verify, a bucket of 256) and any sharded program dense."""
    assert moe.ROW_TILE == 128
    cfg, width = routed(ROUTED[name]), ROUTED[name][3]
    rows, seq_len, sharded = {
        "prefill": (1, width, False),
        "two_row_prefill": (2, width, False),
        "decode_4": (4, 1, False),
        "decode_16": (16, 1, False),
        "decode_32": (32, 1, False),
        "verify_16x3": (16, 3, False),
        "verify_32x5": (32, 5, False),
        "bucket_64": (1, 64, False),
        "bucket_256": (1, 256, False),
        "prefill_under_a_mesh": (1, width, True),
        "decode_32_under_a_mesh": (32, 1, True),
    }[case]
    assert moe.dispatch_path(cfg, rows, seq_len, sharded) == want
    held = cfg.num_held_experts
    picks = cfg.num_experts_per_tok * held / cfg.num_experts
    for share in (1.0, 0.25):
        needed, computed = moe.expert_rows_per_token(
            cfg, seq_len, rows, share, sharded
        )
        assert needed == picks
        if want == "dense":
            assert computed == held
        elif want == "live":
            # the experts its valid tokens are expected to pick between
            # them: never more than are held, nor than their picks
            tokens_ = rows * seq_len * share
            miss = 1 - cfg.num_experts_per_tok / cfg.num_experts
            assert computed == pytest.approx(held * (1 - miss ** tokens_))
            assert computed == moe.expected_live_experts(cfg, tokens_)
            assert computed < min(held, tokens_ * picks) + 1e-9
        else:
            # its own picks and half a tile an expert, never every expert
            assert computed == picks * share + held * 64 / (rows * seq_len)
            assert computed < held / 2


@pytest.mark.parametrize("cell,rows,active,held_of,low,high", [
    ("rag", 16, 4, MIXTRAL, 5.4, 5.5),          # 8 x (1 - 0.75^4)
    ("rag_3_rows", 16, 3, MIXTRAL, 4.6, 4.7),
    ("reason1k", 32, 28, MOONLIGHT, 59.9, 60.1),
    ("longdoc", 16, 9, KEYE, 56, 57),
    ("mixedlen", 32, 20, EXAONE, 11.5, 11.7),
    ("glm", 16, 6, GLM, 2.7, 2.8),
])
def test_the_experts_a_cells_decode_step_is_expected_to_read(
    cell, rows, active, held_of, low, high
):
    """ISSUE 46's arithmetic, as the census computes it: the held experts
    the live rows of each routed cell's decode step pick between them."""
    cfg = routed(held_of)
    _, computed = moe.expert_rows_per_token(cfg, 1, rows, active / rows)
    assert low < computed < high
    assert moe.expected_live_experts(cfg, 0) == 0


def test_the_program_observes_its_mesh(tile8, monkeypatch):
    """``moe_mlp`` has no setting for it: a step traced inside a mesh of
    more than one device (the engine's ``with self.mesh``) sees it and
    stays dense, a prefill and a decode step alike; the same calls outside
    take the grouped and the live path."""
    taken = []
    monkeypatch.setattr(
        moe, "moe_mlp_grouped",
        lambda cfg, p, x, valid=None: taken.append("grouped") or x,
    )
    monkeypatch.setattr(
        moe, "moe_mlp_live",
        lambda cfg, p, x, valid=None: taken.append("live") or x,
    )
    monkeypatch.setattr(
        moe, "_dense_combine", lambda cfg, p, x: taken.append("dense") or x
    )
    p = layer(SOFTMAX)
    step = lambda x: jax.jit(lambda p, x: moe.moe_mlp(SOFTMAX, p, x))(p, x)
    assert not moe.under_mesh()
    step(tokens(1, 48))
    step(tokens(4, 1))
    with build_mesh(MeshConfig(ep=4)):
        assert moe.under_mesh()
        step(tokens(1, 48))
        step(tokens(4, 1))
    with build_mesh(MeshConfig()):
        assert not moe.under_mesh()         # one device shards nothing
    assert taken == ["grouped", "live", "dense", "dense"]


# -- the counters ------------------------------------------------------------


def test_the_engine_counts_dispatches_by_path_and_rows_by_the_rule(
    tile8, monkeypatch
):
    """A CPU engine over a routed model: every prefill-family dispatch wide
    enough for the rule is counted grouped (and runs the kernel), every
    decode dispatch and a bucket that fits one tile live, the bucket
    between them dense; ``moe_expert_rows_computed`` counts the valid
    tokens' own picks plus half a tile an expert where grouped, every
    expert where dense and the experts the valid tokens are expected to
    pick between them where live; a decode dispatch adds its steps times
    the held experts and times the expected live ones to
    ``moe_decode_experts_held`` / ``_live``."""
    from distributed_llm_inference_tpu.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.models import llama
    from distributed_llm_inference_tpu.config import TraceConfig

    cfg = SOFTMAX
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    engine = InferenceEngine(
        cfg, params,
        EngineConfig(max_batch_size=2, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32"),
        CacheConfig(kind="dense"), trace_cfg=TraceConfig(),
    )
    calls = []
    grouped = moe.moe_mlp_grouped
    monkeypatch.setattr(
        moe, "moe_mlp_grouped",
        lambda *a, **k: calls.append(1) or grouped(*a, **k),
    )
    # two rows decode together, then one row beside an empty slot
    active = []
    note = engine.plan.note_dispatch

    def noted(kind, shape, valid_tokens=None, active_rows=None, **kw):
        if kind == "decode":
            active.append(active_rows)
        return note(kind, shape, valid_tokens, active_rows, **kw)

    monkeypatch.setattr(engine.plan, "note_dispatch", noted)
    engine.generate(
        [list(range(1, 28)), list(range(2, 7))],
        SamplingOptions(max_new_tokens=4),
    )
    engine.generate([list(range(3, 15))], SamplingOptions(max_new_tokens=4))
    m = engine.metrics
    seen = [(d[0], tuple(d[1]), d[2])
            for t in engine.flight.snapshot() for d in t.get("dispatches", ())]
    prefills = [d for d in seen if d[0] != "decode"]
    decodes = [d for d in seen if d[0] == "decode"]
    wide = [d for d in prefills if d[1][0] * d[1][1] * K >= E * TILE]
    narrow = [d for d in prefills if d[1][0] * d[1][1] <= TILE]
    between = [d for d in prefills if d not in wide and d not in narrow]
    assert wide and narrow and between and len(decodes) == len(active) == 2
    assert sorted(active) == [1, 2]
    assert calls, "a wide prefill traced the grouped form"
    assert m.get_counter("moe_dispatch_grouped") == len(wide)
    assert m.get_counter("moe_dispatch_live") == len(narrow) + len(decodes)
    assert m.get_counter("moe_dispatch_dense") == len(between)
    layers = cfg.num_expert_layers
    expected = lambda tokens_: E * (1 - (1 - K / E) ** tokens_)
    computed = held = live = 0.0
    for (kind, shape, valid), rows in zip(decodes, active):
        assert shape[0] == 2
        computed += shape[0] * shape[1] * expected(rows)
        held += E * shape[1]
        live += expected(rows) * shape[1]
    for kind, shape, valid in prefills:
        if (kind, shape, valid) in wide:
            computed += valid * K + E * TILE / 2
        elif (kind, shape, valid) in narrow:
            computed += shape[0] * shape[1] * expected(valid)
        else:
            computed += shape[0] * shape[1] * E
    assert m.get_counter("moe_expert_rows_computed") == pytest.approx(
        computed * layers
    )
    assert m.get_counter("moe_decode_experts_held") == held
    assert m.get_counter("moe_decode_experts_live") == pytest.approx(live)
    assert live < held
    # needed rows: the valid tokens' picks, whatever the path
    assert m.get_counter("moe_expert_rows_needed") >= (
        sum(d[2] for d in prefills) * K * layers
    )


# -- through the model -------------------------------------------------------


@pytest.mark.parametrize("stack", ["float32", "int8"])
def test_a_models_prefill_reads_each_layers_experts_out_of_the_stack(
    kernel, tile8, monkeypatch, stack
):
    """``model_apply`` over two routed layers at a grouped shape: the layer
    scan hands the kernel the expert stacks whole (``LayerOf``: a slice a
    step would copy a layer's experts before every call) and each layer
    reads ITS matrices: the logits are dense-combine's."""
    from distributed_llm_inference_tpu.cache.dense import DenseKVCache
    from distributed_llm_inference_tpu.models import llama

    cfg = SIGMOID
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    if stack == "int8":
        params = quantize_params(params, scale_dtype=jnp.float32)
    tokens_ = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 128)
    seen = []
    grouped = moe.moe_mlp_grouped
    monkeypatch.setattr(
        moe, "moe_mlp_grouped",
        lambda cfg, p, x, valid=None: seen.append(p["we_d"]) or grouped(
            cfg, p, x, valid
        ),
    )

    def logits():
        cache = DenseKVCache.create(
            cfg.num_layers, 1, 32, cfg.num_kv_heads, cfg.head_dim, jnp.float32
        )
        return np.asarray(llama.model_apply(
            cfg, params, tokens_, cache, jnp.full((1,), 27, jnp.int32)
        )[0])

    got = logits()
    assert seen and all(isinstance(w, moe.LayerOf) for w in seen)
    assert seen[0].stack is params["layers"]["we_d"]
    monkeypatch.setattr(moe, "dispatch_path", lambda *a, **k: "dense")
    want = logits()
    np.testing.assert_allclose(got[:, :27], want[:, :27], atol=2e-5, rtol=1e-5)


def stack_consumers(closed, stacks):
    """What a traced program does with the arrays ``stacks`` (outer jaxpr
    inputs): the primitives that consume them, looked for through the
    scans and calls they are handed down. A stack handed to a scan as one
    of its ``xs`` is sliced a step: ``"scan_xs"``."""
    found = set()

    def walk(jaxpr, tracked):
        for eqn in jaxpr.eqns:
            hit = [
                i for i, v in enumerate(eqn.invars)
                if isinstance(v, jex_core.Var) and v in tracked
            ]
            if not hit:
                continue
            name = eqn.primitive.name
            if name in ("scan", "pjit", "closed_call"):
                inner = eqn.params["jaxpr"]
                inner = getattr(inner, "jaxpr", inner)
                if name == "scan":
                    carried = eqn.params["num_consts"] + eqn.params["num_carry"]
                    if any(i >= carried for i in hit):
                        found.add("scan_xs")
                walk(inner, {inner.invars[i] for i in hit})
            else:
                found.add(name)

    walk(closed.jaxpr, set(stacks))
    return found


def test_a_models_decode_reads_the_live_experts_out_of_the_whole_stacks(
    kernel, monkeypatch,
):
    """A toy routed engine's greedy decode through ``_decode_scan`` (the
    fused scan over a dense cache) takes the live path and gives the tokens
    of the same engine held to dense-combine; and in the decode scan's
    jaxpr the expert stacks reach the kernel's calls WHOLE: nothing slices
    them on the way (a slice a scan step copies every held expert's
    weights, the bytes the live path sets out not to read), where under
    dense-combine they ride the layer scan's ``xs``."""
    from distributed_llm_inference_tpu.cache.dense import DenseKVCache
    from distributed_llm_inference_tpu.engine import InferenceEngine
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
    from distributed_llm_inference_tpu.models import llama

    cfg = SIGMOID
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    prompts = [list(range(1, 12)), list(range(5, 9)), list(range(40, 60))]

    def decoded():
        engine = InferenceEngine(
            cfg, params,
            EngineConfig(max_batch_size=4, prefill_buckets=(32,),
                         max_seq_len=64, dtype="float32"),
            CacheConfig(kind="dense"),
        )
        out = engine.generate(prompts, SamplingOptions(max_new_tokens=9))
        return out, engine.metrics

    def scan_jaxpr():
        cache = DenseKVCache.create(
            cfg.num_layers, 4, 64, cfg.num_kv_heads, cfg.head_dim, jnp.float32
        )
        alive = jnp.array([1, 1, 0, 1], jnp.int32)
        step = lambda i, logits, st: (
            jnp.argmax(logits, -1).astype(jnp.int32), alive, st, logits
        )
        closed = jax.make_jaxpr(
            lambda params, toks, cache: llama.multi_decode_apply(
                cfg, params, toks, cache, 3, step, jnp.zeros(()), alive
            )
        )(params, jnp.ones((4, 1), jnp.int32), cache)
        leaves = jax.tree_util.tree_leaves((params, None, None))
        stacks = [
            var for var, leaf in zip(closed.jaxpr.invars, leaves)
            if any(leaf is params["layers"][k] for k in moe.GROUPED_STACKS)
        ]
        assert len(stacks) == 3
        return stack_consumers(closed, stacks)

    taken = []
    live = moe.moe_mlp_live
    monkeypatch.setattr(
        moe, "moe_mlp_live",
        lambda cfg, p, x, valid=None: taken.append(
            (x.shape, valid is not None, type(p["we_d"]))
        ) or live(cfg, p, x, valid),
    )
    got, metrics = decoded()
    assert ((4, 1, H), True, moe.LayerOf) in taken
    assert metrics.get_counter("moe_dispatch_live") > 0
    assert metrics.get_counter("moe_dispatch_dense") == 0
    assert scan_jaxpr() == {"pallas_call"}
    monkeypatch.setattr(moe, "dispatch_path", lambda *a, **k: "dense")
    want, metrics = decoded()
    assert metrics.get_counter("moe_dispatch_live") == 0
    assert got == want and all(len(t) == 9 for t in got)
    assert "scan_xs" in scan_jaxpr()
