"""End-to-end distributed serving over the native relay (all in-process).

SURVEY §4 test strategy items (c)+(d): a tiny random-weight model served
through the full node stack — directory, lease heartbeats, 2-node pipeline of
block workers, client-side embed/head — compared against a single-process
oracle. Covers CONFIGS.md's config 2 (a 2-stage pipeline split across 2
server nodes) at test scale.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import ModelConfig
from distributed_llm_inference_tpu.distributed import (
    BlockDirectory,
    DirectoryClient,
    DirectoryService,
    DistributedClient,
    RelayServer,
    ServingNode,
    TaskPool,
    native_available,
)
from distributed_llm_inference_tpu.models import llama

pytestmark = pytest.mark.skipif(
    not native_available(), reason="g++ unavailable to build the native relay"
)

CFG = ModelConfig(
    vocab_size=96,
    hidden_size=32,
    intermediate_size=64,
    num_layers=4,
    num_heads=4,
    num_kv_heads=2,
    head_dim=8,
    max_position_embeddings=128,
)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture()
def cluster(params):
    """relay + directory + two block nodes (layers 0-1 / 2-3)."""
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=3.0) as service:
            n1 = ServingNode(
                relay.port, CFG, {k: v[0:2] for k, v in params["layers"].items()},
                0, 1, max_seq_len=64, heartbeat_s=0.5, lease_ttl=3.0,
                dtype=jnp.float32,
            )
            n2 = ServingNode(
                relay.port, CFG, {k: v[2:4] for k, v in params["layers"].items()},
                2, 3, max_seq_len=64, heartbeat_s=0.5, lease_ttl=3.0,
                dtype=jnp.float32,
            )
            try:
                yield relay, service, n1, n2
            finally:
                n1.stop()
                n2.stop()


def _oracle_greedy(params, prompt, steps):
    cache = DenseKVCache.create(
        CFG.num_layers, 1, 64, CFG.num_kv_heads, CFG.head_dim, jnp.float32
    )
    tokens = jnp.asarray([prompt], jnp.int32)
    logits, cache = llama.model_apply(
        CFG, params, tokens, cache, jnp.full((1,), len(prompt), jnp.int32)
    )
    tok = int(jnp.argmax(logits[0, len(prompt) - 1]))
    out = [tok]
    for _ in range(steps - 1):
        logits, cache = llama.model_apply(
            CFG, params, jnp.asarray([[tok]], jnp.int32), cache,
            jnp.ones((1,), jnp.int32),
        )
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


def test_two_stage_pipeline_matches_oracle(cluster, params):
    relay, *_ = cluster
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        route = client.plan_route()
        assert [n["first_layer"] for n in route] == [0, 2]
        got = client.generate([5, 11, 42], max_new_tokens=6)
    ref = _oracle_greedy(params, [5, 11, 42], 6)
    assert got == ref


def test_interleaved_sessions(cluster, params):
    """Two generations interleave on the same workers without crosstalk."""
    relay, *_ = cluster
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as a, DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as b:
        got_a = a.generate([5, 11, 42], max_new_tokens=4)
        got_b = b.generate([7, 3], max_new_tokens=4)
        got_a2 = a.generate([5, 11, 42], max_new_tokens=4)
    assert got_a == _oracle_greedy(params, [5, 11, 42], 4)
    assert got_b == _oracle_greedy(params, [7, 3], 4)
    assert got_a2 == got_a


def test_dead_node_lease_expires_and_replacement_restores(cluster, params):
    relay, service, n1, n2 = cluster
    n2.stop()  # node withdraws (clean stop also removes its lease)
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        with pytest.raises(LookupError):
            client.plan_route()
        # Replacement node brings layers 2-3 back; routing recovers.
        with ServingNode(
            relay.port, CFG,
            {k: v[2:4] for k, v in params["layers"].items()}, 2, 3,
            max_seq_len=64, heartbeat_s=0.5, lease_ttl=3.0, dtype=jnp.float32,
        ):
            got = client.generate([9, 1, 30], max_new_tokens=4)
    assert got == _oracle_greedy(params, [9, 1, 30], 4)


def test_crashed_node_expires_via_ttl():
    """A node that dies WITHOUT cleanup drops out when its lease lapses."""
    d = BlockDirectory(default_ttl=0.2)
    d.register("nodeA", 0, 3, "q", ttl=0.2)
    assert [n.node_id for n in d.alive()] == ["nodeA"]
    time.sleep(0.3)
    assert d.alive() == []
    with pytest.raises(LookupError):
        d.plan_route(4)


def test_route_prefers_longer_coverage():
    d = BlockDirectory()
    d.register("short", 0, 1, "q1")
    d.register("long", 0, 3, "q2")
    d.register("tail", 2, 3, "q3")
    route = d.plan_route(4)
    assert [n.node_id for n in route] == ["long"]


def test_task_pool_batches_and_propagates_errors():
    calls = []

    def fn(items):
        calls.append(list(items))
        if items[0] == "boom":
            raise RuntimeError("kaboom")
        return [i * 2 for i in items]

    with TaskPool(fn, max_batch=4, window_s=0.05) as pool:
        futs = [pool.submit(i) for i in (1, 2, 3)]
        assert sorted(f.result(5) for f in futs) == [2, 4, 6]
        with pytest.raises(RuntimeError):
            pool("boom", timeout=5)
    assert any(len(c) > 1 for c in calls), "no batching happened"


def test_backend_session_semantics(params):
    """Live sessions are never silently corrupted: admission of an extra
    session fails while all slots are live, idle sessions get LRU-evicted,
    and a decode hop for an unknown session raises instead of fabricating an
    empty cache row."""
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    backend = BlockBackend(
        CFG, {k: v[0:2] for k, v in params["layers"].items()}, 0, 1,
        max_sessions=2, max_seq_len=32, dtype=jnp.float32,
        session_idle_timeout=300.0,
    )
    x = np.zeros((1, 4, CFG.hidden_size), np.float32)
    backend.forward("g1", x, 4, create=True)
    backend.forward("g2", x, 4, create=True)
    assert backend.load == 2
    with pytest.raises(RuntimeError, match="node full"):
        backend.forward("g3", x, 4, create=True)  # both sessions live
    backend.session_idle_timeout = 0.0  # now everything counts as idle
    backend.forward("g2", x, 4)  # touch g2 → g1 is the LRU
    backend.forward("g3", x, 4, create=True)  # evicts idle g1
    assert "g1" not in backend.sessions and "g3" in backend.sessions
    with pytest.raises(KeyError):  # evicted session cannot silently resume
        backend.forward("g1", x, 1)


def test_unknown_session_error_reaches_client(cluster, params):
    """A decode hop for a session a worker lost fails fast at the client."""
    from distributed_llm_inference_tpu.distributed.messages import pack_frame, unpack_frame
    from distributed_llm_inference_tpu.distributed.relay import RelayClient

    relay, _, n1, _ = cluster
    with RelayClient(port=relay.port) as c:
        header = {"op": "forward", "gen_id": "ghost", "num_new": 1,
                  "hops": ["reply.ghost"], "new": False}
        x = np.zeros((1, 1, CFG.hidden_size), np.float32)
        c.put(n1.queue, pack_frame(header, x))
        reply, _ = unpack_frame(c.get("reply.ghost", timeout=10))
    assert reply["op"] == "error"
    assert "ghost" in reply["error"]


def test_unknown_op_drop_is_counted(cluster, params):
    """A frame with an op the worker doesn't speak is dropped but counted —
    protocol skew shows on /metrics instead of looking like request loss."""
    from distributed_llm_inference_tpu.distributed.messages import pack_frame
    from distributed_llm_inference_tpu.distributed.relay import RelayClient

    relay, _, n1, _ = cluster
    with RelayClient(port=relay.port) as c:
        header = {"op": "bogus", "hops": ["reply.nowhere"]}
        x = np.zeros((1, 1, CFG.hidden_size), np.float32)
        c.put(n1.queue, pack_frame(header, x))
    deadline = time.time() + 10
    while time.time() < deadline:
        if n1.metrics.get_counter("unknown_ops_dropped") >= 1:
            break
        time.sleep(0.05)
    assert n1.metrics.get_counter("unknown_ops_dropped") >= 1


def test_midstream_node_death_reroute_and_replay(cluster, params):
    """SURVEY §5.3: a node dies MID-generation; a replacement registers; the
    client re-routes and replays, and the final stream is identical to an
    uninterrupted run."""
    import threading

    relay, service, n1, n2 = cluster
    prompt = [5, 11, 42]
    ref = _oracle_greedy(params, prompt, 8)

    replacement = []
    streamed, dead = threading.Event(), threading.Event()
    seen = []

    def on_token(token):
        # prefill and two decode hops are out: the client stands still until
        # the node is down, so the next hop is the one that meets the loss
        seen.append(token)
        if len(seen) == 3:
            streamed.set()
            assert dead.wait(30.0), "node 2 not stopped 30 s after token 3"

    def kill_and_replace():
        if not streamed.wait(60.0):
            return  # the assertion on ``dead`` below says so
        n2.stop()
        dead.set()
        replacement.append(ServingNode(
            relay.port, CFG,
            {k: v[2:4] for k, v in params["layers"].items()}, 2, 3,
            max_seq_len=64, heartbeat_s=0.5, lease_ttl=3.0, dtype=jnp.float32,
        ))

    killer = threading.Thread(target=kill_and_replace)
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        killer.start()
        try:
            got = client.generate(
                prompt, max_new_tokens=8, timeout=4.0, reroute_wait=20.0,
                on_token=on_token,
            )
        finally:
            streamed.set()  # a generate that raised must not leave it waiting
            killer.join()
            for node in replacement:
                node.stop()
        assert dead.is_set(), f"no third token in 60 s, node 2 not stopped: {seen}"
        assert client.failovers >= 1, "node died but no failover happened"
    assert got == ref


def test_failover_gives_up_after_max_retries(cluster, params):
    relay, service, n1, n2 = cluster
    n2.stop()  # no replacement will come
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        with pytest.raises((LookupError, TimeoutError, RuntimeError)):
            client.generate([5, 11], max_new_tokens=4, timeout=1.0,
                            max_retries=1, reroute_wait=1.0)


def test_prompt_longer_than_bucket_chunked_prefill(cluster, params):
    """Prompts beyond the largest prefill bucket stream through in chunks."""
    relay, *_ = cluster
    prompt = list(np.random.default_rng(3).integers(0, CFG.vocab_size, 19))
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(8,), dtype=jnp.float32
    ) as client:
        got = client.generate(prompt, max_new_tokens=4)
    assert got == _oracle_greedy(params, prompt, 4)


def test_backend_buffer_growth(params):
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    b = BlockBackend(CFG, {k: v[0:2] for k, v in params["layers"].items()},
                     0, 1, max_sessions=2, max_seq_len=128, dtype=jnp.float32)
    first = b.cache.max_len
    assert first < 128
    x = np.zeros((1, 48, CFG.hidden_size), np.float32)
    b.forward("g1", x, 48, create=True)
    assert b.cache.max_len >= 48
    grown = b.cache.max_len
    for i in range(4):
        b.forward("g1", x[:, :1], 1)
    # Exceeding the virtual cap fails loudly.
    b.forward("g2", np.zeros((1, 64, CFG.hidden_size), np.float32), 64,
              create=True)
    from distributed_llm_inference_tpu.distributed.backend import SchemaError
    with pytest.raises(SchemaError, match="max_seq_len"):
        for _ in range(80):
            b.forward("g2", x[:, :1], 1)
    # All sessions gone -> next admission shrinks back.
    b.end("g1"); b.end("g2")
    b.forward("g3", x[:, :1], 1, create=True)
    assert b.cache.max_len <= grown
    assert b.cache.max_len == b._windows[0]


def test_forward_many_batches_and_matches_serial(params):
    """N sessions' decode hops in ONE device call == N serial row calls."""
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    layer_p = {k: v[0:2] for k, v in params["layers"].items()}
    serial = BlockBackend(CFG, layer_p, 0, 1, max_sessions=4, max_seq_len=64,
                          dtype=jnp.float32)
    batched = BlockBackend(CFG, layer_p, 0, 1, max_sessions=4, max_seq_len=64,
                           dtype=jnp.float32)
    rng = np.random.default_rng(0)
    x0 = rng.normal(size=(3, 1, 4, CFG.hidden_size)).astype(np.float32)

    # Prefill (create) hops, one session per row.
    ys = [serial.forward(f"g{i}", x0[i], 4, create=True) for i in range(3)]
    yb = batched.forward_many(
        [(f"g{i}", x0[i], 4, True) for i in range(3)]
    )
    assert batched.batched_calls == 1 and batched.batched_items == 3
    for a, b in zip(ys, yb):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

    # Decode hops.
    x1 = rng.normal(size=(3, 1, 1, CFG.hidden_size)).astype(np.float32)
    ys = [serial.forward(f"g{i}", x1[i], 1) for i in range(3)]
    yb = batched.forward_many([(f"g{i}", x1[i], 1, False) for i in range(3)])
    assert batched.batched_calls == 2
    for a, b in zip(ys, yb):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_forward_many_isolates_per_item_errors(params):
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    layer_p = {k: v[0:2] for k, v in params["layers"].items()}
    be = BlockBackend(CFG, layer_p, 0, 1, max_sessions=4, max_seq_len=64,
                      dtype=jnp.float32)
    x = np.zeros((1, 1, CFG.hidden_size), np.float32)
    out = be.forward_many([
        ("a", x, 1, True),
        ("ghost", x, 1, False),   # decode for unknown session
        ("b", x, 1, True),
    ])
    assert isinstance(out[1], KeyError)
    assert isinstance(out[0], np.ndarray) and isinstance(out[2], np.ndarray)


def test_forward_many_same_session_hops_stay_ordered(params):
    """Two hops for ONE session in a batch: the second defers, not corrupts."""
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    layer_p = {k: v[0:2] for k, v in params["layers"].items()}
    ref = BlockBackend(CFG, layer_p, 0, 1, max_sessions=4, max_seq_len=64,
                       dtype=jnp.float32)
    dup = BlockBackend(CFG, layer_p, 0, 1, max_sessions=4, max_seq_len=64,
                       dtype=jnp.float32)
    rng = np.random.default_rng(1)
    xa = rng.normal(size=(1, 1, CFG.hidden_size)).astype(np.float32)
    xb = rng.normal(size=(1, 1, CFG.hidden_size)).astype(np.float32)
    ref.forward("g", xa, 1, create=True)
    y2 = ref.forward("g", xb, 1)
    out = dup.forward_many([("g", xa, 1, True), ("g", xb, 1, False)])
    np.testing.assert_allclose(out[1], y2, rtol=2e-5, atol=2e-5)


def test_concurrent_clients_batch_on_node(cluster, params):
    """N concurrent generations through one 2-node chain: correct tokens AND
    the nodes actually coalesce hops into batched device calls."""
    import threading

    relay, service, n1, n2 = cluster
    # Widen the linger so concurrent decode hops reliably co-batch.
    n1._pool.window_s = n2._pool.window_s = 0.05

    prompts = [[3, 14, 15], [9, 2, 6], [5, 35, 5]]
    refs = [_oracle_greedy(params, p, 6) for p in prompts]
    outs = [None] * len(prompts)
    errs = []

    def drive(i):
        try:
            with DistributedClient(relay.port, CFG, params,
                                   dtype=jnp.float32) as c:
                outs[i] = c.generate(prompts[i], max_new_tokens=6)
        except Exception as e:  # pragma: no cover
            errs.append(repr(e))

    threads = [threading.Thread(target=drive, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs, errs
    assert outs == refs
    assert n1.backend.batched_calls > 0 or n2.backend.batched_calls > 0, (
        "no hop was ever co-batched"
    )


def test_batched_step_does_not_corrupt_idle_full_session(params):
    """A co-batched step must not touch an idle session whose length equals
    the cache buffer width (the masked write regression: an unconditional
    per-row write clamps into the idle row's last real token)."""
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    layer_p = {k: v[0:2] for k, v in params["layers"].items()}
    be = BlockBackend(CFG, layer_p, 0, 1, max_sessions=4, max_seq_len=32,
                      dtype=jnp.float32)
    rng = np.random.default_rng(7)
    # Fill session A to exactly the first window bucket (32 = max_seq_len).
    xa = rng.normal(size=(1, 32, CFG.hidden_size)).astype(np.float32)
    be.forward("a", xa, 32, create=True)
    k_before = np.asarray(be.cache.k[:, 0]).copy()
    # Two other sessions co-batch a decode hop; A is idle in the batch.
    xb = rng.normal(size=(2, 1, 1, CFG.hidden_size)).astype(np.float32)
    be.forward_many([("b", xb[0], 1, True), ("c", xb[1], 1, True)])
    assert be.batched_calls == 1
    np.testing.assert_array_equal(np.asarray(be.cache.k[:, 0]), k_before)


def test_quantized_backend_close_to_bf16(params):
    """int8/int4-weight + int8-KV node output stays close to the exact
    backend (the reference's int8 serving-node optimization, utils/model.py:93-123)."""
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    layer_p = {k: v[0:2] for k, v in params["layers"].items()}
    exact = BlockBackend(CFG, layer_p, 0, 1, max_seq_len=64, dtype=jnp.float32)
    rng = np.random.default_rng(11)
    x0 = rng.normal(size=(1, 8, CFG.hidden_size)).astype(np.float32)
    x1 = rng.normal(size=(1, 1, CFG.hidden_size)).astype(np.float32)
    y_ref = [exact.forward("g", x0, 8, create=True), exact.forward("g", x1, 1)]

    for quantize, kv_quant in (("int8", None), ("int8", "int8"), ("int4", None)):
        be = BlockBackend(CFG, layer_p, 0, 1, max_seq_len=64,
                          dtype=jnp.float32, quantize=quantize,
                          kv_quant=kv_quant)
        ys = [be.forward("g", x0, 8, create=True), be.forward("g", x1, 1)]
        for a, b_ in zip(y_ref, ys):
            cos = float((a * b_).sum() / (np.linalg.norm(a) * np.linalg.norm(b_)))
            assert cos > 0.98, (quantize, kv_quant, cos)


def test_int8_nodes_e2e_matches_bf16_oracle(params):
    """Full chain with int8-weight, int8-KV nodes: greedy streams agree with
    the exact oracle on (at least) their first tokens and run to length."""
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=3.0):
            n1 = ServingNode(
                relay.port, CFG, {k: v[0:2] for k, v in params["layers"].items()},
                0, 1, max_seq_len=64, dtype=jnp.float32,
                quantize="int8", kv_quant="int8",
            )
            n2 = ServingNode(
                relay.port, CFG, {k: v[2:4] for k, v in params["layers"].items()},
                2, 3, max_seq_len=64, dtype=jnp.float32,
                quantize="int8", kv_quant="int8",
            )
            try:
                with DistributedClient(relay.port, CFG, params,
                                       dtype=jnp.float32) as c:
                    out = c.generate([3, 14, 15], max_new_tokens=6)
                ref = _oracle_greedy(params, [3, 14, 15], 6)
                assert len(out) == 6
                # int8 noise can flip later near-tie argmaxes on random
                # weights; the stream must at least start identically.
                assert out[0] == ref[0], (out, ref)
            finally:
                n1.stop()
                n2.stop()


def test_concurrent_generations_one_client(cluster, params):
    """N interleaved generations on ONE client instance (per-generation
    relay connections + reply queues) through the 2-node chain."""
    import threading

    relay, service, n1, n2 = cluster
    prompts = [[3, 14, 15], [9, 2, 6], [5, 35, 5], [7, 7, 7]]
    refs = [_oracle_greedy(params, p, 5) for p in prompts]
    outs = [None] * len(prompts)
    errs = []
    with DistributedClient(relay.port, CFG, params, dtype=jnp.float32) as c:
        def drive(i):
            try:
                outs[i] = c.generate(prompts[i], max_new_tokens=5)
            except Exception as e:  # pragma: no cover
                errs.append(repr(e))
        threads = [threading.Thread(target=drive, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert not errs, errs
    assert outs == refs


def test_distributed_sampling_reproducible(cluster, params):
    """Sampling options ride the distributed path: same seed, same stream;
    stochastic differs from greedy."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    relay, service, n1, n2 = cluster
    opts = SamplingOptions(temperature=1.0, top_p=0.9)
    with DistributedClient(relay.port, CFG, params, dtype=jnp.float32) as c:
        a = c.generate([3, 14, 15], max_new_tokens=6, options=opts, seed=5)
        b_ = c.generate([3, 14, 15], max_new_tokens=6, options=opts, seed=5)
        g = c.generate([3, 14, 15], max_new_tokens=6)
    assert a == b_
    assert len(a) == 6
    assert a != g  # overwhelmingly likely at temperature 1.0


@pytest.mark.slow
def test_control_plane_restart_mid_generation(params):
    """Chaos: the relay + directory restart MID-generation. Workers
    re-register via lease lapse (worker.py health loop), reply connections
    transparently re-dial, and the client's failover replays the stream."""
    import threading

    relay = RelayServer()
    port = relay.port
    service = DirectoryService(port, default_ttl=2.0)
    mk_node = lambda lo, hi: ServingNode(
        port, CFG, {k: v[lo:hi] for k, v in params["layers"].items()},
        lo, hi - 1, max_seq_len=64, heartbeat_s=0.3, lease_ttl=2.0,
        dtype=jnp.float32,
    )
    n1, n2 = mk_node(0, 2), mk_node(2, 4)
    prompt = [3, 14, 15]
    ref = _oracle_greedy(params, prompt, 10)
    result, errs = [], []

    def drive():
        try:
            with DistributedClient(port, CFG, params, dtype=jnp.float32) as c:
                result.append(c.generate(
                    prompt, max_new_tokens=10, timeout=8.0,
                    max_retries=4, reroute_wait=20.0,
                ))
        except Exception as e:
            errs.append(repr(e))

    t = threading.Thread(target=drive)
    try:
        t.start()
        time.sleep(0.7)  # let the generation get going
        # Kill the control plane mid-stream...
        service.stop()
        relay.stop()
        time.sleep(0.5)
        # ...and bring it back on the SAME port.
        relay = RelayServer(port=port)
        service = DirectoryService(port, default_ttl=2.0)
        t.join(timeout=120)
        assert not t.is_alive(), "generation hung after control-plane restart"
        assert not errs, errs
        assert result and result[0] == ref
        # Workers re-registered: full coverage is routable again.
        route = DirectoryClient(port).route(CFG.num_layers)
        assert route
    finally:
        n1.stop()
        n2.stop()
        service.stop()
        relay.stop()


# -- directory-driven block assignment (r4: server.py:8's "choose optimal
#    block ids" intent) -------------------------------------------------------


def test_assign_policy_gap_then_thinnest():
    d = BlockDirectory()
    # Empty deployment: first joiner takes the whole model (default span).
    assert d.assign(4) == (0, 3)
    d.register("a", 0, 1, "qa")
    # Layers 2-3 uncovered: a span-2 joiner gets exactly the hole.
    assert d.assign(4, span=2) == (2, 3)
    d.register("b", 2, 3, "qb")
    # Full coverage: add redundancy where replication is thinnest.
    d.register("a2", 0, 1, "qa2")  # layers 0-1 now x2
    assert d.assign(4, span=2) == (2, 3)
    # A tail gap shorter than span yields a SHORTER range anchored at the
    # gap (drifting the range backward to use the full span would add
    # redundancy instead of prioritizing the hole).
    d2 = BlockDirectory()
    d2.register("head", 0, 2, "qh")
    assert d2.assign(4, span=3) == (3, 3)
    with pytest.raises(ValueError):
        d.assign(4, span=0)


def test_spare_auto_adopts_dead_nodes_range(cluster, params):
    """Kill one node; a spare started with NO operator-chosen layers asks
    the directory, adopts the dead range, and serving recovers — the
    elastic-recovery story without a human in the loop (the r3 version of
    this test hand-specified the replacement's --layers)."""
    relay, service, n1, n2 = cluster
    n2.stop()
    with DirectoryClient(relay.port) as d:
        # The lease is already gone (clean stop removes it); the directory
        # advertises the hole to the next joiner.
        first, last = d.assign(CFG.num_layers)
        assert (first, last) == (2, 3)
    with ServingNode(
        relay.port, CFG,
        {k: v[first : last + 1] for k, v in params["layers"].items()},
        first, last, max_seq_len=64, heartbeat_s=0.5, lease_ttl=3.0,
        dtype=jnp.float32,
    ):
        with DistributedClient(
            relay.port, CFG, params, prefill_buckets=(16,),
            dtype=jnp.float32,
        ) as client:
            got = client.generate([9, 1, 30], max_new_tokens=4)
    assert got == _oracle_greedy(params, [9, 1, 30], 4)


def test_spare_auto_adopts_after_ttl_crash(cluster, params):
    """A CRASHED node (no clean removal) re-opens its range when the lease
    lapses: assign() then hands the hole to a spare."""
    relay, service, n1, n2 = cluster
    # Simulate a crash: stop the node's threads WITHOUT removing the lease.
    # Join the health loop first so no in-flight full-TTL heartbeat can be
    # applied after the test shortens the lease (a real crash has no
    # surviving heartbeat thread either).
    n2._stop.set()
    n2._health_thread.join(timeout=5)
    service.directory.heartbeat(n2.node_id, ttl=0.2)  # shorten remaining TTL
    time.sleep(0.4)
    with DirectoryClient(relay.port) as d:
        assert d.assign(CFG.num_layers) == (2, 3)


def test_assign_reservation_spreads_concurrent_spares():
    """Two spares joining concurrently (each minutes from registering)
    must be steered to DIFFERENT holes: assign(reserve_ttl=...) records a
    pending lease counted as coverage but never routed to."""
    d = BlockDirectory()
    d.register("mid", 1, 2, "qm")  # holes at layer 0 and layer 3
    a = d.assign(4, span=1, reserve_ttl=5.0)
    b = d.assign(4, span=1, reserve_ttl=5.0)
    assert {a, b} == {(0, 0), (3, 3)}
    # Reservations cover layers for assign() but are NOT routable.
    with pytest.raises(LookupError):
        d.plan_route(4)
    # An expired reservation re-opens its hole.
    d2 = BlockDirectory()
    d2.register("mid", 1, 3, "qm")
    assert d2.assign(4, span=1, reserve_ttl=0.01) == (0, 0)
    time.sleep(0.05)
    assert d2.assign(4, span=1) == (0, 0)


# -- cache kinds + local tp behind the relay (SURVEY §5.8 two-tier compose) --


def test_tp_sharded_nodes_match_oracle(params):
    """Two relay nodes, each tp=2 over local (virtual) chips: the block's
    weights and KV shard over the node's mesh with XLA inserting the
    all-reduces, while the relay protocol — and the client — are unchanged.
    The reference's worker intent (serve ``block_index_start..end`` on
    whatever hardware the node has, ``server/worker.py:13-14``) on a
    multi-chip host."""
    from distributed_llm_inference_tpu.config import MeshConfig

    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=3.0):
            with ServingNode(
                relay.port, CFG,
                {k: v[0:2] for k, v in params["layers"].items()}, 0, 1,
                max_seq_len=64, heartbeat_s=0.5, lease_ttl=3.0,
                dtype=jnp.float32, mesh_cfg=MeshConfig(tp=2),
            ) as n1, ServingNode(
                relay.port, CFG,
                {k: v[2:4] for k, v in params["layers"].items()}, 2, 3,
                max_seq_len=64, heartbeat_s=0.5, lease_ttl=3.0,
                dtype=jnp.float32, mesh_cfg=MeshConfig(tp=2),
            ) as n2:
                assert n1.backend.mesh is not None
                assert n2.backend.mesh is not None
                # The sharding is real: a weight leaf lives on 2 devices.
                wq = n1.backend.params["wq"]
                assert len(wq.sharding.device_set) == 2
                with DistributedClient(
                    relay.port, CFG, params, prefill_buckets=(16,),
                    dtype=jnp.float32,
                ) as client:
                    got = client.generate([5, 11, 42], max_new_tokens=6)
    assert got == _oracle_greedy(params, [5, 11, 42], 6)


def test_tp_sharded_node_rejects_cross_host_axes(params):
    from distributed_llm_inference_tpu.config import MeshConfig
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    with pytest.raises(ValueError, match="tp only"):
        BlockBackend(
            CFG, {k: v[0:2] for k, v in params["layers"].items()}, 0, 1,
            dtype=jnp.float32, mesh_cfg=MeshConfig(pp=2),
        )


def _oracle_greedy_sink(params, prompt, steps, window, sinks):
    from distributed_llm_inference_tpu.cache.sink import SinkKVCache

    cache = SinkKVCache.create(
        CFG.num_layers, 1, window, sinks, CFG.num_kv_heads, CFG.head_dim,
        jnp.float32,
    )
    tokens = jnp.asarray([prompt], jnp.int32)
    logits, cache = llama.model_apply(
        CFG, params, tokens, cache, jnp.full((1,), len(prompt), jnp.int32)
    )
    tok = int(jnp.argmax(logits[0, len(prompt) - 1]))
    out = [tok]
    for _ in range(steps - 1):
        logits, cache = llama.model_apply(
            CFG, params, jnp.asarray([[tok]], jnp.int32), cache,
            jnp.ones((1,), jnp.int32),
        )
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


def test_sink_node_streams_past_window(params):
    """A relay node serving its block with the SINK cache decodes a stream
    LONGER than its window — the reference's headline bounded-memory feature
    ("Distributed implementation of sink cache",
    ``models/llama/cache.py:8-10``) in the reference's own distributed
    setting. Output matches a single-process sink-cache oracle exactly."""
    from distributed_llm_inference_tpu.config import CacheConfig

    window, sinks, steps = 24, 4, 40
    cc = CacheConfig(kind="sink", window_length=window, num_sink_tokens=sinks)
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=3.0):
            with ServingNode(
                relay.port, CFG, params["layers"], 0, CFG.num_layers - 1,
                max_seq_len=32,  # sink streams are NOT capped by this
                heartbeat_s=0.5, lease_ttl=3.0, dtype=jnp.float32,
                cache_cfg=cc,
            ):
                with DistributedClient(
                    relay.port, CFG, params, prefill_buckets=(16,),
                    dtype=jnp.float32,
                ) as client:
                    got = client.generate([5, 11, 42], max_new_tokens=steps)
    assert len(got) == steps  # well past window=24: memory stayed fixed
    assert got == _oracle_greedy_sink(params, [5, 11, 42], steps, window,
                                      sinks)


def test_paged_node_growth_matches_dense(params):
    """A paged-pool node grows sessions page-by-page (allocator + batched
    table installs) and its outputs match the dense backend bit-for-bit."""
    from distributed_llm_inference_tpu.config import CacheConfig
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    block = {k: v[0:2] for k, v in params["layers"].items()}
    paged = BlockBackend(
        CFG, block, 0, 1, max_sessions=2, max_seq_len=64, dtype=jnp.float32,
        cache_cfg=CacheConfig(kind="paged", page_size=8, num_pages=32),
    )
    dense = BlockBackend(
        CFG, block, 0, 1, max_sessions=2, max_seq_len=64, dtype=jnp.float32,
    )
    rng = np.random.default_rng(0)
    x0 = rng.standard_normal((1, 16, CFG.hidden_size)).astype(np.float32)
    yp = paged.forward("g", x0, 12, create=True)
    yd = dense.forward("g", x0, 12, create=True)
    np.testing.assert_allclose(yp[:, :12], yd[:, :12], rtol=2e-5, atol=2e-5)
    for i in range(10):
        x = rng.standard_normal((1, 1, CFG.hidden_size)).astype(np.float32)
        yp = paged.forward("g", x, 1)
        yd = dense.forward("g", x, 1)
        np.testing.assert_allclose(yp, yd, rtol=2e-4, atol=2e-4)
    # 12 + 10 = 22 tokens at page_size=8 → the session grew to 3 pages.
    slot = paged.sessions["g"][0]
    assert len(paged._slot_pages[slot]) == 3
    # Ending the session returns its pages to the pool.
    free_before = paged.allocator.free_count
    paged.end("g")
    assert paged.allocator.free_count == free_before + 3


def test_paged_node_pool_exhaustion_fails_cleanly(params):
    """Pool pressure on a paged node fails the REQUEST (node_full-class error
    the client can retry elsewhere), never the node."""
    from distributed_llm_inference_tpu.config import CacheConfig
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    backend = BlockBackend(
        CFG, {k: v[0:2] for k, v in params["layers"].items()}, 0, 1,
        max_sessions=4, max_seq_len=64, dtype=jnp.float32,
        cache_cfg=CacheConfig(kind="paged", page_size=8, num_pages=6),
    )
    x = np.zeros((1, 16, CFG.hidden_size), np.float32)
    backend.forward("a", x, 16, create=True)  # 2 of the 5 usable pages
    backend.forward("b", x, 16, create=True)  # 2 more
    with pytest.raises(RuntimeError, match="node full"):
        backend.forward("c", x, 16, create=True)  # needs 2, only 1 left
    # The starved admission was rolled back — no empty session squats a slot.
    assert "c" not in backend.sessions
    # Live sessions are unaffected, and the remaining page still serves
    # session a's growth past its page boundary (16 → 17 tokens).
    y1 = backend.forward("a", np.ones((1, 1, CFG.hidden_size), np.float32), 1)
    assert np.isfinite(np.asarray(y1)).all()


def test_sink_node_tp_composes(params):
    """Cache kind × local mesh compose: a tp=2 node serving the sink ring."""
    from distributed_llm_inference_tpu.config import CacheConfig, MeshConfig
    from distributed_llm_inference_tpu.distributed.backend import BlockBackend

    backend = BlockBackend(
        CFG, params["layers"], 0, CFG.num_layers - 1, max_sessions=2,
        dtype=jnp.float32,
        cache_cfg=CacheConfig(kind="sink", window_length=24,
                              num_sink_tokens=4),
        mesh_cfg=MeshConfig(tp=2),
    )
    rng = np.random.default_rng(1)
    x = rng.standard_normal((1, 8, CFG.hidden_size)).astype(np.float32)
    y = backend.forward("g", x, 8, create=True)
    for _ in range(30):  # stream past the 24-token window
        y = backend.forward(
            "g", rng.standard_normal((1, 1, CFG.hidden_size)
                                     ).astype(np.float32), 1)
    assert np.isfinite(np.asarray(y)).all()


def test_generate_many_matches_serial_byte_exact(cluster, params):
    """The batched client decode loop is a pure perf feature: same seeds,
    same tokens, byte for byte, as N serial ``generate`` calls."""
    relay, *_ = cluster
    prompts = [[5, 11, 42], [7, 3], [9, 1, 30, 2, 8]]
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        serial = [client.generate(p, max_new_tokens=6) for p in prompts]
        many = client.generate_many(prompts, max_new_tokens=6)
    assert many == serial
    assert serial[0] == _oracle_greedy(params, prompts[0], 6)


def test_generate_many_per_row_budgets_and_eos(cluster, params):
    """Per-row max_new_tokens and per-row EOS masking: early-finishing
    rows drop out of the lockstep batch without perturbing survivors."""
    relay, *_ = cluster
    prompts = [[5, 11, 42], [7, 3], [9, 1, 30]]
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        budgets = [3, 6, 2]
        serial = [
            client.generate(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)
        ]
        many = client.generate_many(prompts, max_new_tokens=budgets)
        assert many == serial
        assert [len(m) for m in many] == budgets
        # EOS mid-stream on one row only: pick row 0's 2nd token as eos.
        eos = serial[0][1]
        serial_eos = [
            client.generate(p, max_new_tokens=6, eos_token_id=eos)
            for p in prompts
        ]
        many_eos = client.generate_many(prompts, max_new_tokens=6,
                                        eos_token_id=eos)
    assert many_eos == serial_eos
    assert many_eos[0][-1] == eos and len(many_eos[0]) <= 2


def test_generate_many_sampling_matches_serial(cluster, params):
    """Stochastic sampling stays byte-exact: each batched row folds the
    same per-row key/step the serial path would, via vmap."""
    from distributed_llm_inference_tpu.engine.sampling import SamplingOptions

    relay, *_ = cluster
    prompts = [[5, 11, 42], [7, 3], [9, 1, 30]]
    opts = SamplingOptions(temperature=1.0, top_k=0, top_p=0.9)
    seeds = [5, 6, 7]
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        serial = [
            client.generate(p, max_new_tokens=5, options=opts, seed=s)
            for p, s in zip(prompts, seeds)
        ]
        many = client.generate_many(prompts, max_new_tokens=5,
                                    options=opts, seeds=seeds)
    assert many == serial


def test_generate_many_mixed_prefill_buckets(cluster, params):
    """A cohort whose prompts end prefill in DIFFERENT buckets (different
    padded S) still samples its first tokens correctly — the first-token
    path gathers each row's last valid position before stacking instead
    of concatenating ragged ``[1, bucket, H]`` slices."""
    relay, *_ = cluster
    # Buckets (4, 16): lengths 2 and 3 pad to 4, length 6 pads to 16.
    prompts = [[5, 11, 42], [7, 3, 9, 1, 30, 2], [8, 4]]
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(4, 16), dtype=jnp.float32
    ) as client:
        serial = [client.generate(p, max_new_tokens=5) for p in prompts]
        many = client.generate_many(prompts, max_new_tokens=5)
    assert many == serial


def test_generate_many_rejects_mismatched_row_args(cluster, params):
    """Per-row argument lists shorter/longer than the cohort fail up front
    with a clear ValueError, not a mid-flight IndexError."""
    relay, *_ = cluster
    prompts = [[5, 11], [7, 3]]
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        with pytest.raises(ValueError, match="max_new_tokens"):
            client.generate_many(prompts, max_new_tokens=[3])
        with pytest.raises(ValueError, match="options"):
            client.generate_many(prompts, max_new_tokens=3,
                                 options=[None, None, None])
        with pytest.raises(ValueError, match="seeds"):
            client.generate_many(prompts, max_new_tokens=3, seeds=[1])


def test_worker_rejects_malformed_stacked_frame(cluster, params):
    """A stacked frame whose gens/num_new/payload row counts disagree gets
    an explicit per-row error reply — dropped rows must never leave the
    client waiting out its full hop timeout."""
    from distributed_llm_inference_tpu.distributed.messages import (
        pack_frame, unpack_frame,
    )
    from distributed_llm_inference_tpu.distributed.relay import RelayClient

    relay, _, n1, _ = cluster
    with RelayClient(port=relay.port) as c:
        header = {"op": "forward", "gens": ["ma", "mb"], "num_new": [1],
                  "hops": ["reply.mal"], "new": True, "seq": 0}
        x = np.zeros((2, 1, CFG.hidden_size), np.float32)
        c.put(n1.queue, pack_frame(header, x))
        seen = {}
        for _ in range(2):
            reply, _ = unpack_frame(c.get("reply.mal", timeout=10))
            assert reply["op"] == "error"
            assert reply["code"] == "schema"
            seen[reply["gen_id"]] = reply["error"]
    assert set(seen) == {"ma", "mb"}
    assert n1.metrics.snapshot().get("malformed_frames") == 1


def test_client_connection_pool_reuses_relay(cluster, params):
    """Satellite: one dialed connection serves many generations — the
    pool returns clean connections for reuse across calls."""
    relay, *_ = cluster
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        client.generate([5, 11, 42], max_new_tokens=3)
        client.generate([7, 3], max_new_tokens=3)
        client.generate_many([[5, 11, 42], [7, 3]], max_new_tokens=3)
        snap = client.metrics.snapshot()
    assert snap.get("connections_opened") == 1


def test_api_gateway_batched_client_backend(cluster, params):
    """Gateway opt-in to the batched loop: concurrent HTTP requests are
    grouped into one generate_many cohort and still return the exact
    greedy tokens each request would get alone."""
    import http.client
    import json
    import threading

    from distributed_llm_inference_tpu.config import ServingConfig
    from distributed_llm_inference_tpu.serving import ApiServer
    from distributed_llm_inference_tpu.serving.backends import ClientBackend

    relay, *_ = cluster
    prompts = [[5, 11, 42], [7, 3]]
    with DistributedClient(
        relay.port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32
    ) as client:
        backend = ClientBackend(client, request_timeout_s=30.0,
                                batch_max=4, batch_window_s=0.05)
        server = ApiServer(backend, ServingConfig(host="127.0.0.1", port=0))
        server.start()
        try:
            results = {}

            def post(i, prompt):
                conn = http.client.HTTPConnection(
                    "127.0.0.1", server.port, timeout=60
                )
                conn.request(
                    "POST", "/v1/completions",
                    json.dumps({"prompt": prompt, "max_tokens": 4}),
                    {"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                results[i] = (resp.status, json.loads(resp.read()))
                conn.close()

            threads = [
                threading.Thread(target=post, args=(i, p))
                for i, p in enumerate(prompts)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            server.request_shutdown()
            server.join(timeout=30.0)
        for i, p in enumerate(prompts):
            status, doc = results[i]
            assert status == 200, doc
            choice = doc["choices"][0]
            assert choice["token_ids"] == _oracle_greedy(params, p, 4)
            assert choice["finish_reason"] == "length"
        # The collector actually grouped work (vs per-request threads).
        snap = backend.metrics.snapshot()
        assert snap.get("client_batch_group_count", 0) >= 1
