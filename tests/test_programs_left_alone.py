"""What ISSUE 58 did not convert did not change: the programs of the
configurations the benchmark serves, at their rehearsal sizes, trace to the
jaxprs they traced to on the PARENT of PR 58 (commit 738f3c5).

``prefill`` is ``model_apply`` (head last) through a row's view of the
engine's own cache, at the engine's first pad width: pinned for the six
configurations whose prefill-family dispatches never reach the changed branch
(the latent pools, the int8 pool under a learned selection, the value-dtype
pool under the kernel and under the mesh). ``decode`` is the engine's own
``_decode_scan`` (the jitted ``_decode_k``; the mesh engine's one-token
``_decode_step``): pinned for all ten (the eleven cells' configurations: two
cells share Mistral's), since a decode step (S == 1) keeps
``block_apply.step`` and every kernel as they were.
The other five configurations' prefills are the change itself
(``tests/test_prefill_pool_inplace.py`` holds them to the scatter form's pool
and logits).

The digests were taken with this file run as a script against that tree
(``cd <parent checkout>; XLA_FLAGS=--xla_force_host_platform_device_count=8
JAX_PLATFORMS=cpu PYTHONPATH=. python <this file>``), under this suite's
``conftest.py`` (its matmul precision is in the jaxprs); jax 0.9.0. A
later change to what these programs trace to is not this test's business to
forbid: regenerate, and say why.

**ISSUE 61** (the in-place decode sweep copies a live page's scale rows
itself: ``ops/paged_attention.py:joined_scale_rows``) left all sixteen
standing, the int8 pools' four too: at the rehearsal's sizes (pages of 8, a
table of 128 positions) no decode reaches the in-place sweep, and the latent
pools' copies' form there keeps the gather (a 64-lane page: the fall-back by
the shape). What it changed is pinned below at a size that reaches it
(``IN_PLACE``: the same rehearsal engines over pages of 64, a table pinned at
1024 positions): the decode programs of the four pool classes the copies' form
serves, taken on PR 61's own tree, because the parent's held the gather this
PR removes (``tests/test_paged_attention.py`` holds the two forms bit-equal);
the fifth, pages of 32, is the shape that keeps the gather, and its program IS
the parent's (digest taken on commit 6669722 and again here).
"""

import contextlib
import functools
import hashlib
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import server
from distributed_llm_inference_tpu.config import (
    CacheConfig, EngineConfig, MeshConfig, ModelConfig,
)
from distributed_llm_inference_tpu.engine import engine as engine_mod
from distributed_llm_inference_tpu.engine.plan import AttentionPlan
from distributed_llm_inference_tpu.engine.sampling import SamplingParams
from distributed_llm_inference_tpu.models import llama

#: whose prefill-family dispatches ISSUE 58 left on the programs they had
LEFT_ALONE = (
    "moonlight-16b-a3b", "xing4.0-29b-a4b", "glm-5.2", "keye-vl2-30b-a3b",
    "brumby-14b", "mistral-7b-bf16-tp4",
)
CONVERTED = (
    "mistral-7b", "mixtral-8x7b-8l", "k-exaone-236b-a23b", "ouro-2.6b",
)

PINNED = {
    "moonlight-16b-a3b.prefill": "99506a0f7f6255b1",
    "moonlight-16b-a3b.decode": "b732de7eccfc2eff",
    "xing4.0-29b-a4b.prefill": "977cd9b12eb8c83b",
    "xing4.0-29b-a4b.decode": "1d00a9ef30faec6b",
    "glm-5.2.prefill": "fd0bf93e5589faf0",
    "glm-5.2.decode": "6af404d633a6cfc2",
    "keye-vl2-30b-a3b.prefill": "b75928f123b7b9f5",
    "keye-vl2-30b-a3b.decode": "97de04c50cc86211",
    "brumby-14b.prefill": "403d6eecef8892a1",
    "brumby-14b.decode": "becaef97c3ddd4e4",
    "mistral-7b-bf16-tp4.prefill": "57c0eea6ada3fcc7",
    "mistral-7b-bf16-tp4.decode": "eb4d4f112cc3b70d",
    "mistral-7b.decode": "d4102929eb504b3d",
    "mixtral-8x7b-8l.decode": "c4cb56fd8ca7c554",
    "k-exaone-236b-a23b.decode": "e8c10455ef6bdcce",
    "ouro-2.6b.decode": "ff95058790b15936",
}


#: ISSUE 61: configuration -> (page size, the pool class its fused scan sweeps
#: in place, the decode program's digest at that page size)
IN_PLACE = {
    "mistral-7b": (64, "full", "42cba1fad476071b"),
    "k-exaone-236b-a23b": (64, "window and full", "6af89384f188f980"),
    "keye-vl2-30b-a3b": (64, "indexed", "4b3bf57ba7b9b75f"),
    "ouro-2.6b": (64, "a looped stack's", "ee4b31f513648af3"),
    "mixtral-8x7b-8l": (32, "the shape fall-back", "26ebc1fd074b8607"),
}


def digest(jaxpr) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def engine_of(name, page=None):
    """The engine ``benchmark/server.py`` builds for the configuration's CPU
    rehearsal (the plan answers for the chip, so the kernels' branches are
    the ones traced), over abstract weights' worth of real tiny ones.
    ``page``: over pages of that size instead, the table pinned at 1024
    positions (no ladder), where the fused scan sweeps the pool in place."""
    conf = server.load_config(
        os.path.join(server.REPO, "benchmark", "configs", f"{name}.json"), True
    )
    serve = conf["serve"]
    cfg = ModelConfig.from_hf_config(server.hf_block(conf))
    mesh_cfg = MeshConfig(**serve["mesh"]) if serve.get("mesh") else None
    mesh = None
    if mesh_cfg is not None:
        from distributed_llm_inference_tpu.parallel import build_mesh

        mesh = build_mesh(mesh_cfg)
    maker = importlib.import_module(f"benchmark.weights.{serve['weight_maker']}")
    params = maker.make(
        cfg, 0, jnp.dtype(serve["dtype"]), serve["weights"], mesh=mesh
    )
    ekw = dict(serve["engine"])
    if "prefill_buckets" in ekw:
        ekw["prefill_buckets"] = tuple(ekw["prefill_buckets"])
    if page is not None:
        ekw.update(max_seq_len=1024, decode_windows=())
        serve["cache"] = {
            **serve["cache"], "page_size": page,
            "max_pages_per_session": 1024 // page,
        }
    was = engine_mod.AttentionPlan
    engine_mod.AttentionPlan = functools.partial(AttentionPlan, backend="tpu")
    try:
        return cfg, engine_mod.InferenceEngine(
            cfg, params, EngineConfig(dtype=serve["dtype"], **ekw),
            CacheConfig(**serve["cache"]), mesh_cfg=mesh_cfg,
        )
    finally:
        engine_mod.AttentionPlan = was


def scan_args(engine):
    b = engine.batch
    return (
        engine.params, jnp.zeros((b, 1), jnp.int32), engine.cache,
        jnp.ones((b,), jnp.bool_), jax.random.PRNGKey(0),
        SamplingParams.create(b), jnp.zeros((b,), jnp.int32),
        jnp.full((b,), 8, jnp.int32),
    )


@functools.lru_cache(maxsize=None)
def in_place(name):
    """``(engine, its decode program's text)`` at ``IN_PLACE``'s page size."""
    _, engine = engine_of(name, IN_PLACE[name][0])
    return engine, str(jax.make_jaxpr(engine._decode_k)(*scan_args(engine)))


@functools.lru_cache(maxsize=None)
def programs(name) -> dict:
    cfg, engine = engine_of(name)
    plan = engine.plan
    width = plan.chunk_tokens if plan.enabled else plan.buckets[0]
    out = {}

    def prefill(params, tokens, cache, n_valid):
        sub = cache.select_row(0)
        logits, sub = llama.model_apply(
            cfg, params, tokens, sub, n_valid[None], head="last"
        )
        return logits, cache.merge_row(sub, 0)

    mesh = engine.mesh
    with (mesh if mesh is not None else contextlib.nullcontext()):
        if name in LEFT_ALONE:
            out[f"{name}.prefill"] = digest(jax.make_jaxpr(prefill)(
                engine.params, jnp.zeros((1, width), jnp.int32), engine.cache,
                jnp.int32(3),
            ))
        if engine.decode_steps > 1:
            out[f"{name}.decode"] = digest(
                jax.make_jaxpr(engine._decode_k)(*scan_args(engine))
            )
        else:
            out[f"{name}.decode"] = digest(
                jax.make_jaxpr(engine._decode)(*scan_args(engine)[:6])
            )
    return out


@pytest.mark.parametrize("program", sorted(PINNED))
def test_the_program_traces_to_what_it_traced_to_on_the_parent(program):
    name = program.rsplit(".", 1)[0]
    assert programs(name)[program] == PINNED[program]
    assert set(programs(name)) <= set(PINNED)


@pytest.mark.parametrize("name", sorted(IN_PLACE))
def test_the_in_place_decode_program_gathers_no_scale_rows(name):
    """The fused scan's program over a table wide enough for the in-place
    sweep: pinned, and holding no ``[rows, table slots, kv heads, page]``
    float32 array anywhere (what the wrapper's gather of every slot's scale
    rows made a plane a layer a step) unless the page size is the shape that
    keeps the gather."""
    page, _, pinned = IN_PLACE[name]
    engine, text = in_place(name)
    rows, slots = engine.cache.page_table.shape
    gathered = f"f32[{rows},{slots},{engine.cache.k_pages.shape[2]},{page}]"
    assert (gathered in text) == (page == 32)
    assert digest(text) == pinned


@pytest.mark.parametrize("name", sorted(IN_PLACE))
def test_the_scale_row_counters_of_an_engine_of_each_pool_class(name):
    """``decode_scale_rows_by_page`` / ``decode_scale_rows_gathered`` over a
    few fused decode dispatches of a served engine: the kernel copied the
    scale rows of every live page of both stored planes and the wrapper
    gathered none; at the page size that keeps the gather, the reverse."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    page = IN_PLACE[name][0]
    engine, _ = in_place(name)
    m = engine.metrics
    before = {k: m.get_counter(k) for k in (
        "decode_pages_live", "decode_scale_rows_by_page",
        "decode_scale_rows_gathered",
    )}
    for prompt in ([1, 2, 3, 4, 5], list(range(1, 12))):
        engine.submit(prompt, SamplingOptions(max_new_tokens=20))
    for _ in range(4):
        engine.step()
    got = {k: m.get_counter(k) - v for k, v in before.items()}
    live = got["decode_pages_live"]
    assert live > 0
    rows, slots = engine.cache.page_table.shape
    layers = sum(n for _, n in engine.plan.attention_layers)
    if page == 32:
        assert got["decode_scale_rows_by_page"] == 0
        assert got["decode_scale_rows_gathered"] % (
            rows * slots * 2 * layers * engine.decode_steps
        ) == 0 < got["decode_scale_rows_gathered"]
    else:
        assert got["decode_scale_rows_by_page"] == 2 * live
        assert got["decode_scale_rows_gathered"] == 0


if __name__ == "__main__":  # the digests, to paste
    import json
    import sys

    sys.path.insert(0, os.path.dirname(__file__))
    import conftest  # noqa: F401  (the suite's platform, devices and precision)

    found = {}
    for n in LEFT_ALONE + CONVERTED:
        found.update(programs(n))
    for n in IN_PLACE:
        found[f"{n}.decode-in-place"] = digest(in_place(n)[1])
    print(json.dumps(found, indent=4))
