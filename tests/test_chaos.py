"""Fault-injection suite: real multi-node generation under seeded faults.

The transport-hardening contract under test (ISSUE 2):

* every fault class the injector supports — drop / delay / duplicate /
  truncate / corrupt / sever — leaves the token stream BYTE-EXACT against
  the single-process oracle (failover replays; seq dedup kills
  at-least-once duplicates; CRC turns corruption into loss),
* a corrupted frame is never delivered to a model layer (the hub drops a
  bad-CRC PUT at ingress; the client rejects a bad-CRC reply),
* `RelayClient` survives a hub restart via bounded backoff, and a
  concurrent `close()` surfaces as ConnectionError, never AttributeError,
* a restarted `DirectoryService` is re-populated by the workers'
  lease-lapsed heartbeat path,
* the gateway's circuit breaker opens on backend failure (503 +
  Retry-After) and recovers through half-open probes,
* all of it is observable: failover / duplicate / breaker counters in
  ``Metrics.prometheus()``.

Determinism: every schedule is a seeded :class:`FaultPlan`; the only
sleeps are injected delays and bounded condition-polling loops.
"""

import asyncio
import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_llm_inference_tpu.cache.dense import DenseKVCache
from distributed_llm_inference_tpu.config import ModelConfig, ServingConfig
from distributed_llm_inference_tpu.distributed import (
    ChaosProxy,
    ChaosRelayClient,
    DirectoryService,
    DistributedClient,
    FaultPlan,
    FaultRule,
    RelayClient,
    RelayServer,
    ServingNode,
    native_available,
)
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.serving import ApiServer
from distributed_llm_inference_tpu.serving.backends import (
    Backend,
    Handle,
    TokenEvent,
)
from distributed_llm_inference_tpu.serving.breaker import CircuitBreaker
from distributed_llm_inference_tpu.utils.metrics import Metrics

pytestmark = [
    pytest.mark.chaos,
    pytest.mark.skipif(
        not native_available(),
        reason="g++ unavailable to build the native relay",
    ),
]

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

PROMPT = [5, 11, 42]
STEPS = 6


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


@pytest.fixture()
def cluster(params):
    """relay + directory + two block nodes (layers 0-1 / 2-3), all on the
    clean path; tests interpose a ChaosProxy for the client side only."""
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=3.0) as service:
            n1 = ServingNode(
                relay.port, CFG,
                {k: v[0:2] for k, v in params["layers"].items()},
                0, 1, max_seq_len=64, heartbeat_s=0.5, lease_ttl=3.0,
                dtype=jnp.float32,
            )
            n2 = ServingNode(
                relay.port, CFG,
                {k: v[2:4] for k, v in params["layers"].items()},
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


# -- FaultPlan / FaultRule ----------------------------------------------------


def test_fault_rule_parse_and_validation():
    r = FaultRule.parse("drop:block.*:put:after=3,count=2")
    assert (r.kind, r.queue, r.op, r.after, r.count) == (
        "drop", "block.*", "put", 3, 2
    )
    r2 = FaultRule.parse("delay:*:any:delay_s=0.25,prob=0.5,count=none")
    assert r2.count is None and r2.prob == 0.5 and r2.delay_s == 0.25
    with pytest.raises(ValueError):
        FaultRule.parse("explode:*:any")  # unknown kind
    with pytest.raises(ValueError):
        FaultRule.parse("drop:*")  # missing op
    with pytest.raises(ValueError):
        FaultRule.parse("drop:*:put:bogus=1")  # unknown option


def test_fault_plan_deterministic_replay():
    def run():
        plan = FaultPlan.from_specs(
            ["drop:block.*:put:prob=0.5,count=none,after=1"], seed=1234
        )
        fired = [
            plan.decide("block.n1", "put") is not None for _ in range(50)
        ]
        return fired, list(plan.injected)

    a, ia = run()
    b, ib = run()
    assert a == b and ia == ib
    assert any(a) and not all(a)  # prob actually probabilistic
    assert a[0] is False  # after=1 skips the first match


def test_fault_plan_count_and_matching():
    plan = FaultPlan(
        [FaultRule("drop", queue="block.*", op="put", count=2)], seed=0
    )
    hits = [
        plan.decide(q, op) is not None
        for q, op in [
            ("client.x", "put"),  # queue mismatch
            ("block.a", "get"),  # op mismatch
            ("block.a", "put"),
            ("block.b", "put"),
            ("block.c", "put"),  # count exhausted
        ]
    ]
    assert hits == [False, False, True, True, False]


def test_fault_plan_corrupt_is_seeded_and_never_noop():
    payload = b"some-frame-payload"
    a = FaultPlan(seed=9).corrupt(payload)
    b = FaultPlan(seed=9).corrupt(payload)
    assert a == b and a != payload and len(a) == len(payload)


# -- transport hardening (raw relay level) ------------------------------------


def test_hub_drops_corrupt_put_at_ingress():
    """A PUT whose payload is damaged after the CRC was computed must be
    rejected by the hub — the consumer sees a LOST frame, never garbage —
    and the connection itself keeps working."""
    with RelayServer() as srv, RelayClient(port=srv.port) as c:
        frame = bytearray(RelayClient._encode_put("cq", b"payload-bytes"))
        frame[-1] ^= 0x01
        c._sock.sendall(bytes(frame))
        with pytest.raises(TimeoutError):
            c.get("cq", timeout=0.5)
        c.put("cq", b"good")
        assert c.get("cq", timeout=2) == b"good"


def test_corrupt_reply_is_lost_never_garbage():
    """A reply damaged on the hub→client leg fails the client-side CRC:
    surfaced as loss (timeout after the recycled connection re-parks),
    and the recycled connection works again."""
    plan = FaultPlan([FaultRule("corrupt", queue="q", op="reply")], seed=3)
    with RelayServer() as srv, ChaosRelayClient(
        port=srv.port, plan=plan
    ) as c:
        c.put("q", b"reply-bytes")
        with pytest.raises((ConnectionError, TimeoutError)):
            c.get("q", timeout=1.0)
        assert plan.injected == [("corrupt", "q", "reply")]
        c.put("q", b"after")
        assert c.get("q", timeout=2) == b"after"


def test_reconnect_backoff_survives_hub_restart():
    """A hub restart of under a second must not permanently wedge a
    long-lived client: ops during the outage fail as lost frames, but the
    client keeps re-dialing with backoff and recovers."""
    srv = RelayServer()
    port = srv.port
    c = RelayClient(port=port, reconnect_timeout_s=8.0)
    srv2 = []
    try:
        c.put("q", b"one")
        assert c.get("q", timeout=2) == b"one"
        srv.stop()

        def restart():
            time.sleep(0.6)
            srv2.append(RelayServer(port=port))

        t = threading.Thread(target=restart, daemon=True)
        t.start()
        deadline = time.monotonic() + 20
        ok = False
        while time.monotonic() < deadline and not ok:
            try:
                c.put("q", b"two")
                ok = c.get("q", timeout=2) == b"two"
            except (ConnectionError, OSError, TimeoutError):
                continue
        t.join(timeout=5)
        assert ok, "client never recovered after hub restart"
        assert c.reconnects >= 1
    finally:
        c.close()
        for s in srv2:
            s.stop()


def test_reconnect_gives_up_within_budget():
    srv = RelayServer()
    c = RelayClient(port=srv.port, reconnect_timeout_s=0.5)
    srv.stop()
    t0 = time.monotonic()
    with pytest.raises((ConnectionError, OSError)):
        c.put("q", b"x")  # may buffer silently...
        c.get("q", timeout=0.5)  # ...but the next op must fail fast
    assert time.monotonic() - t0 < 5.0
    c.close()


def test_closed_client_raises_connection_error():
    with RelayServer() as srv:
        c = RelayClient(port=srv.port)
        c.close()
        with pytest.raises(ConnectionError):
            c.get("q", timeout=0.5)
        with pytest.raises(ConnectionError):
            c.put("q", b"x")


def test_concurrent_close_is_connection_error_not_attribute_error():
    """close() racing a parked get() nulls the socket; the getter must see
    the ConnectionError family (the condition its callers handle)."""
    with RelayServer() as srv:
        c = RelayClient(port=srv.port)
        errs = []
        parked = threading.Event()

        def g():
            parked.set()
            try:
                c.get("q", timeout=5)
            except BaseException as e:  # noqa: BLE001 - recording for assert
                errs.append(e)

        t = threading.Thread(target=g, daemon=True)
        t.start()
        parked.wait(2)
        time.sleep(0.1)  # let the GET park server-side
        c.close()
        t.join(timeout=5)
        assert not t.is_alive()
        assert errs, "parked get returned instead of raising"
        assert isinstance(errs[0], (ConnectionError, OSError, TimeoutError))
        assert not isinstance(errs[0], AttributeError)


# -- end-to-end generation under faults ---------------------------------------


def _warm_nodes(relay_port, params, prompts, steps=STEPS):
    """The same generation over the clean path first, so the nodes' programs
    are compiled before a fault is judged: the hops below have 2 s each, and
    on a busy machine a first hop that still has to compile outlasts that —
    the replay then outruns a duplicated frame and nothing is left for the
    worker to dedupe."""
    with DistributedClient(
        relay_port, CFG, params, prefill_buckets=(16,), dtype=jnp.float32,
    ) as client:
        if len(prompts) == 1:
            client.generate(prompts[0], max_new_tokens=steps, timeout=60.0)
        else:
            client.generate_many(prompts, max_new_tokens=steps, timeout=60.0)


def _generate_through_chaos(relay_port, params, plan, max_retries=3,
                            steps=STEPS):
    """One full generation with ALL client traffic (data + directory)
    routed through a chaos proxy; returns (tokens, streamed, client)."""
    streamed = []
    _warm_nodes(relay_port, params, [PROMPT], steps)
    with ChaosProxy("127.0.0.1", relay_port, plan=plan) as proxy:
        with DistributedClient(
            proxy.port, CFG, params, prefill_buckets=(16,),
            dtype=jnp.float32,
        ) as client:
            got = client.generate(
                PROMPT, max_new_tokens=steps, timeout=2.0,
                max_retries=max_retries, reroute_wait=10.0,
                on_token=streamed.append,
            )
            return got, streamed, client


FAULT_CASES = [
    # (spec, expect_failover)
    ("drop:block.*:put:after=2,count=1", True),
    ("corrupt:block.*:put:after=2,count=1", True),
    ("corrupt:client.*:reply:after=1,count=1", True),
    ("sever:block.*:put:after=2,count=1", True),
    ("truncate:block.*:put:after=2,count=1", True),
    ("delay:block.*:put:delay_s=0.2,count=3", False),
    ("duplicate:block.*:put:after=1,count=2", False),
    ("duplicate:client.*:reply:after=1,count=1", False),
]


@pytest.mark.parametrize("spec,expect_failover", FAULT_CASES,
                         ids=[c[0].split(":")[0] + "-" + c[0].split(":")[1]
                              for c in FAULT_CASES])
def test_generation_byte_exact_under_fault(cluster, params, spec,
                                           expect_failover):
    relay, _service, n1, n2 = cluster
    plan = FaultPlan.from_specs([spec], seed=42)
    got, streamed, client = _generate_through_chaos(relay.port, params, plan)
    ref = _oracle_greedy(params, PROMPT, STEPS)
    assert got == ref, f"token stream diverged under {spec}"
    # No dropped, duplicated, or reordered tokens on the streaming hook
    # either (a failover replay must not re-emit replayed tokens).
    assert streamed == got
    assert plan.injected, f"fault {spec} never fired"
    # Corruption must never reach a model layer: the workers saw no
    # malformed frame (hub/client CRC turned it into loss instead).
    assert n1.errors == [] and n2.errors == []
    if expect_failover:
        assert client.failovers >= 1
        assert client.metrics.get_counter("failovers") >= 1
        assert "dli_failovers_total" in client.metrics.prometheus()
    if spec.startswith("duplicate:block"):
        skipped = (n1.metrics.get_counter("duplicate_hops_skipped")
                   + n2.metrics.get_counter("duplicate_hops_skipped"))
        assert skipped >= 1, "worker never deduped the duplicated hop"
    if spec.startswith("duplicate:client"):
        assert client.metrics.get_counter("stale_replies_discarded") >= 1


@pytest.mark.slow
def test_generation_survives_fault_storm(cluster, params):
    """Several fault classes at once, probabilistic, unlimited count —
    the seeded plan keeps it replayable; byte-exactness must hold."""
    relay, *_ = cluster
    plan = FaultPlan.from_specs(
        [
            "drop:block.*:put:prob=0.1,count=none",
            "duplicate:block.*:put:prob=0.15,count=none",
            "delay:client.*:reply:prob=0.2,count=none,delay_s=0.05",
            "corrupt:client.*:reply:prob=0.1,count=2",
        ],
        seed=7,
    )
    got, streamed, _client = _generate_through_chaos(
        relay.port, params, plan, max_retries=8, steps=8
    )
    assert got == _oracle_greedy(params, PROMPT, 8)
    assert streamed == got
    assert plan.injected, "storm fired nothing (seed drift?)"


def test_directory_restart_mid_generation(cluster, params):
    """Kill + restart the DirectoryService while a generation is in
    flight: the data plane finishes byte-exact, and the workers
    re-register through the lease-lapsed heartbeat path so routing
    resumes against the fresh (empty) directory."""
    relay, service, n1, n2 = cluster
    # Injected per-hop delay stretches the generation so the restart
    # lands mid-flight (no wall-clock pacing of the generation itself).
    plan = FaultPlan(
        [FaultRule("delay", queue="block.*", op="put", delay_s=0.15,
                   count=None)],
        seed=0,
    )
    first_token = threading.Event()
    results = {}

    def run():
        try:
            streamed = []
            with ChaosProxy("127.0.0.1", relay.port, plan=plan) as proxy:
                with DistributedClient(
                    proxy.port, CFG, params, prefill_buckets=(16,),
                    dtype=jnp.float32,
                ) as client:
                    results["out"] = client.generate(
                        PROMPT, max_new_tokens=8, timeout=5.0,
                        max_retries=3, reroute_wait=15.0,
                        on_token=lambda t: (
                            streamed.append(t), first_token.set()
                        ),
                    )
                    results["streamed"] = streamed
        except BaseException as e:  # noqa: BLE001 - surfaced by the assert
            results["err"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert first_token.wait(timeout=60), "generation never started"
    service.stop()  # directory gone mid-generation
    new_service = DirectoryService(relay.port, default_ttl=3.0)
    try:
        t.join(timeout=120)
        assert not t.is_alive()
        assert "err" not in results, f"generation failed: {results.get('err')}"
        assert results["out"] == _oracle_greedy(params, PROMPT, 8)
        assert results["streamed"] == results["out"]
        # Workers re-register via heartbeat -> ok=False -> register; the
        # fresh directory then routes the full chain again.
        with DistributedClient(
            relay.port, CFG, params, prefill_buckets=(16,),
            dtype=jnp.float32,
        ) as probe_client:
            deadline = time.monotonic() + 15
            while True:
                try:
                    route = probe_client.plan_route()
                    break
                except (LookupError, TimeoutError):
                    assert time.monotonic() < deadline, (
                        "workers never re-registered after directory restart"
                    )
                    time.sleep(0.2)
            assert [n["first_layer"] for n in route] == [0, 2]
            # And generation works end to end on the recovered cluster.
            again = probe_client.generate(PROMPT, max_new_tokens=4,
                                          timeout=5.0)
            assert again == _oracle_greedy(params, PROMPT, 4)
    finally:
        # The nodes go while a directory still answers. Stopped after it (by
        # the fixture), a node's stop() asks a directory that is gone while
        # its health thread's heartbeat waits on the same client: each ends
        # the other's socket timeout, and one of them then waits for ever
        # (three whole runs of five hung here, PR 63).
        n1.stop()
        n2.stop()
        new_service.stop()


def test_worker_stop_is_prompt_with_long_heartbeat(params):
    """Satellite: _health_loop waits on the stop event, so stop() returns
    promptly even with a 30s heartbeat interval."""
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=60.0):
            node = ServingNode(
                relay.port, CFG,
                {k: v[0:2] for k, v in params["layers"].items()},
                0, 1, max_seq_len=64, heartbeat_s=30.0, lease_ttl=60.0,
                dtype=jnp.float32,
            )
            t0 = time.monotonic()
            node.stop()
            assert time.monotonic() - t0 < 5.0


# -- circuit breaker ----------------------------------------------------------


def test_breaker_state_machine_and_probe_semantics():
    t = [0.0]
    m = Metrics()
    b = CircuitBreaker(failure_threshold=3, recovery_s=10.0,
                       success_threshold=2, metrics=m, clock=lambda: t[0])
    assert b.allow() and b.state == "closed"
    b.record_failure()
    b.record_success()  # resets the consecutive-failure streak
    b.record_failure()
    b.record_failure()
    assert b.state == "closed"
    b.record_failure()
    assert b.state == "open" and not b.allow()
    assert b.retry_after() >= 1.0
    b.record_probe(True)  # a healthy probe cannot close an OPEN breaker
    assert b.state == "open"
    t[0] = 10.0
    assert b.state == "half_open"
    assert b.allow() and b.allow() and not b.allow()  # trial budget == 2
    b.record_failure()  # trial failed: re-open
    assert b.state == "open"
    t[0] = 20.0
    b.record_probe(True)
    b.record_probe(True)
    assert b.state == "closed"
    assert m.get_counter("breaker_open_transitions") == 2
    assert m.get_counter("breaker_closed_transitions") == 1
    assert m.get_gauge("breaker_state") == 0.0
    assert "dli_breaker_state 0" in m.prometheus()


class _StubBackend(Backend):
    """Minimal backend for gateway-level breaker tests: instant one-token
    completions, health toggled by the test."""

    def __init__(self):
        self.metrics = Metrics()
        self.healthy = True

    def start(self, loop):
        self._loop = loop

    def submit(self, prompt, options, deadline):
        h = Handle(gen_id="g", queue=asyncio.Queue())
        h.queue.put_nowait(TokenEvent(7, False))
        h.queue.put_nowait(TokenEvent(-1, True, "length"))
        return h

    def cancel(self, handle):
        pass

    def active_sessions(self):
        return 0

    def queue_depth(self):
        return 0

    def probe(self):
        return self.healthy

    def stop(self, timeout=10.0):
        pass


def _post(port, body, timeout=30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(
        "POST", "/v1/completions", json.dumps(body),
        {"Content-Type": "application/json"},
    )
    return conn, conn.getresponse()


def _get(port, path, timeout=10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    return conn, conn.getresponse()


def test_gateway_breaker_opens_and_recovers():
    backend = _StubBackend()
    scfg = ServingConfig(
        host="127.0.0.1", port=0,
        breaker_failure_threshold=2, breaker_recovery_s=0.4,
        breaker_probe_interval_s=0.05,
    )
    server = ApiServer(backend, scfg)
    server.start()
    try:
        conn, resp = _post(server.port, {"prompt": [1], "max_tokens": 1})
        assert resp.status == 200
        resp.read()
        conn.close()

        backend.healthy = False  # probes now fail -> breaker opens
        deadline = time.monotonic() + 10
        while server.breaker.state != "open":
            assert time.monotonic() < deadline, "breaker never opened"
            time.sleep(0.02)
        conn, resp = _post(server.port, {"prompt": [1], "max_tokens": 1})
        assert resp.status == 503
        assert resp.getheader("Retry-After") is not None
        doc = json.loads(resp.read())
        conn.close()
        assert doc["error"]["code"] == "breaker_open"

        conn, resp = _get(server.port, "/healthz")
        hz = json.loads(resp.read())
        conn.close()
        assert hz["breaker"] == "open"
        conn, resp = _get(server.port, "/metrics")
        text = resp.read().decode()
        conn.close()
        assert "dli_breaker_state 1" in text
        assert "dli_breaker_open_transitions_total" in text
        assert "dli_http_503_breaker_total" in text

        backend.healthy = True  # probes recover it: open -> half -> closed
        deadline = time.monotonic() + 10
        while server.breaker.state != "closed":
            assert time.monotonic() < deadline, "breaker never closed"
            time.sleep(0.02)
        conn, resp = _post(server.port, {"prompt": [1], "max_tokens": 1})
        assert resp.status == 200
        resp.read()
        conn.close()
    finally:
        server.request_shutdown()
        server.join(timeout=30.0)


# -- batched decode under faults ----------------------------------------------


@pytest.mark.parametrize("spec", [
    "drop:block.*:put:after=2,count=1",
    "duplicate:block.*:put:after=1,count=2",
], ids=["drop", "duplicate"])
def test_generate_many_byte_exact_under_fault(cluster, params, spec):
    """The batched decode loop inherits the transport contract: a dropped
    stacked frame replays the whole unfinished cohort on a fresh route; a
    duplicated one is deduped per-gen by the worker — either way every
    row's tokens stay byte-exact vs serial generation."""
    relay, _service, n1, n2 = cluster
    prompts = [[5, 11, 42], [7, 3], [9, 1, 30]]
    plan = FaultPlan.from_specs([spec], seed=42)
    _warm_nodes(relay.port, params, prompts)
    with ChaosProxy("127.0.0.1", relay.port, plan=plan) as proxy:
        with DistributedClient(
            proxy.port, CFG, params, prefill_buckets=(16,),
            dtype=jnp.float32,
        ) as client:
            streamed = [[] for _ in prompts]
            many = client.generate_many(
                prompts, max_new_tokens=STEPS, timeout=2.0,
                max_retries=4, reroute_wait=10.0,
                on_token=lambda row, tok: streamed[row].append(tok),
            )
            failovers = client.metrics.get_counter("failovers")
    refs = [_oracle_greedy(params, p, STEPS) for p in prompts]
    assert many == refs, f"batched stream diverged under {spec}"
    # on_token fired exactly once per fresh token, even across replays.
    assert streamed == many
    assert plan.injected, f"fault {spec} never fired"
    assert n1.errors == [] and n2.errors == []
    if spec.startswith("drop"):
        assert failovers >= 1
    else:
        skipped = (n1.metrics.get_counter("duplicate_hops_skipped")
                   + n2.metrics.get_counter("duplicate_hops_skipped"))
        assert skipped >= 1, "worker never deduped the duplicated frame"
