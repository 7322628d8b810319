"""Elastic fleet controller suite (fleet/).

Covers the drain / rebalance / autoscale subsystem end to end:

* cost model — the bytes-vs-latency arbiter's decision flips at the
  configured crossovers, every ``decide()`` tallies exactly one
  decision counter, online EMA observations move the crossover, and the
  page-ship size gate removes that option;
* placement policy — routable-row filtering (draining / dead / pending
  rows excluded), deterministic least-loaded tiebreaks, hot-node
  detection, and the directory's ``draining`` heartbeat flag;
* page shipping — ``export_prefix_pages`` → ``encode_pages`` →
  ``decode_pages`` → ``import_prefix_pages`` round-trips device pages
  BIT-EXACT into a second engine's pool (greedy continuation parity),
  and truncated payloads are rejected;
* the gateway's cost-model placement probe (``_place_cost``) over a
  fake directory snapshot;
* the controller — autoscale hysteresis (scale-out only after the load
  holds, floor restore, drain-then-fence scale-in) against directory
  rows, and live drain / rebalance / crash-racing-drain over a real
  relay with two ``DecodeNode`` pools: every reshape keeps the
  client-visible stream byte-exact vs an uninterrupted run — zero
  tokens lost, zero duplicated (dense and paged, f32 and int8 KV).
"""

import asyncio
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_llm_inference_tpu.config import (
    CacheConfig,
    DisaggConfig,
    EngineConfig,
    FleetConfig,
    ModelConfig,
    PrefixConfig,
)
from distributed_llm_inference_tpu.disagg import (
    DecodeNode,
    decode_pages,
    encode_pages,
)
from distributed_llm_inference_tpu.distributed.directory import (
    BlockDirectory,
    DirectoryClient,
    DirectoryService,
)
from distributed_llm_inference_tpu.distributed.relay import (
    RelayServer,
    native_available,
)
from distributed_llm_inference_tpu.engine.engine import InferenceEngine
from distributed_llm_inference_tpu.engine.sampling import SamplingOptions
from distributed_llm_inference_tpu.fleet import (
    CostModel,
    FleetController,
    hot_rows,
    least_loaded,
    live_decode_rows,
)
from distributed_llm_inference_tpu.models import llama
from distributed_llm_inference_tpu.prefixstore.spill import HostSpillArena
from distributed_llm_inference_tpu.serving import FleetBackend
from distributed_llm_inference_tpu.utils.metrics import Metrics

pytestmark = [pytest.mark.fleet, pytest.mark.disagg]

needs_native = pytest.mark.skipif(
    not native_available(), reason="g++ unavailable to build the native relay"
)

CFG = ModelConfig(
    vocab_size=128, hidden_size=64, intermediate_size=160, num_layers=2,
    num_heads=4, num_kv_heads=2, head_dim=16,
)
PARAMS = llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)

COMBOS = [
    ("paged", None, 0.0),
    ("paged", "int8", 0.8),
    ("dense", None, 0.8),
    ("dense", "int8", 0.0),
]

OPTS = dict(max_new_tokens=48)  # room for an in-flight reshape


def make_engine(kind="paged", kv_quant=None, batch=2, prefix=False,
                decode_steps=None):
    """``decode_steps=1``: a token a tick, for the tests that must catch a
    48-token stream in mid-run (three ticks of a fused engine)."""
    return InferenceEngine(
        CFG, PARAMS,
        EngineConfig(max_batch_size=batch, prefill_buckets=(8, 16, 32),
                     max_seq_len=64, dtype="float32",
                     decode_steps=decode_steps),
        CacheConfig(kind=kind, kv_quant=kv_quant, page_size=8, num_pages=64,
                    max_pages_per_session=8, prefix_caching=prefix),
    )


def drain_engine(engine, gid, budget_s=60.0):
    toks = []
    deadline = time.monotonic() + budget_s
    while time.monotonic() < deadline:
        for g, tok, fin in engine.step():
            if g != gid:
                continue
            if tok >= 0:
                toks.append(tok)
            if fin:
                return toks
    raise AssertionError("generation did not finish in budget")


# -- cost model ---------------------------------------------------------------


def _cm(metrics=None, **kw):
    return CostModel(FleetConfig(**kw), metrics)


def test_cost_model_decision_flips_at_crossovers():
    # Queueing dominated: the holder is barely busier, prefill is dear,
    # the wire is slow -> stay on the holder.
    cm = _cm(queue_s_per_load=0.01, prefill_s_per_token=1.0,
             wire_bytes_per_s=1e3)
    assert cm.decide(64, holder_load=2, alt_load=1) == "query_move"
    # Same pool state, fat wire, dear prefill -> ship the pages.
    cm = _cm(queue_s_per_load=10.0, prefill_s_per_token=1.0,
             wire_bytes_per_s=1e12)
    assert cm.decide(64, holder_load=2, alt_load=1) == "page_ship"
    # Cheap prefill beats both a deep queue and a slow wire -> migrate.
    cm = _cm(queue_s_per_load=10.0, prefill_s_per_token=1e-9,
             wire_bytes_per_s=1e3)
    assert cm.decide(64, holder_load=2, alt_load=1) == "migrate"
    # Holder no busier than the target: query_move costs 0 and wins the
    # deterministic tie order.
    assert _cm().decide(64, holder_load=1, alt_load=1) == "query_move"


def test_cost_model_counters_tally_every_decision():
    m = Metrics()
    cm = CostModel(FleetConfig(queue_s_per_load=10.0, wire_bytes_per_s=1e12,
                               prefill_s_per_token=1.0), m)
    for _ in range(3):
        cm.decide(64, holder_load=5, alt_load=0)   # page_ship
    for _ in range(2):
        cm.decide(64, holder_load=1, alt_load=1)   # query_move
    assert m.get_counter("fleet_pages_fetched") == 3
    assert m.get_counter("fleet_query_moved") == 2
    assert m.get_counter("fleet_migrated") == 0
    total = sum(m.get_counter(k) for k in
                ("fleet_query_moved", "fleet_pages_fetched", "fleet_migrated"))
    assert total == 5  # exactly one counter per decide()


def test_cost_model_ema_observation_moves_the_crossover():
    cm = _cm(queue_s_per_load=10.0, prefill_s_per_token=0.1,
             wire_bytes_per_s=1e12, cost_ema_alpha=1.0,
             kv_bytes_per_token=4096.0)
    assert cm.decide(64, holder_load=5, alt_load=0) == "page_ship"
    # One measured transfer shows the wire is actually dreadful: a full
    # 8 s for a tiny payload. The next decision flips to migrate.
    cm.observe_ship(nbytes=1024, seconds=8.0)
    assert cm.wire_bytes_per_s == pytest.approx(256.0)
    assert cm.decide(64, holder_load=5, alt_load=0) == "migrate"
    # Degenerate samples are ignored, not folded in.
    cm.observe_ship(nbytes=0, seconds=1.0)
    cm.observe_prefill(tokens=10, seconds=0.0)
    assert cm.wire_bytes_per_s == pytest.approx(256.0)
    assert cm.prefill_s_per_token == pytest.approx(0.1)


def test_cost_model_page_ship_size_gate():
    # The prefix is bigger than the ship budget: page_ship is off the
    # table even though its estimate would win.
    cm = _cm(queue_s_per_load=10.0, prefill_s_per_token=0.1,
             wire_bytes_per_s=1e12, kv_bytes_per_token=4096.0,
             page_ship_max_bytes=1024)
    assert cm.decide(64, holder_load=5, alt_load=0) == "migrate"


# -- placement policy + directory draining flag -------------------------------


def _row(nid, load=0, **kw):
    return {"node_id": nid, "role": "decode", "load": load,
            "queue": f"decode.{nid}", **kw}


def test_live_decode_rows_filters():
    rows = [
        _row("a", 1),
        _row("b", 2, draining=True),
        _row("c", 3),
        _row("d", 0, pending=True),
        {"node_id": "p", "role": "prefill", "load": 0},
    ]
    assert [r["node_id"] for r in live_decode_rows(rows)] == ["a", "c"]
    assert [r["node_id"] for r in live_decode_rows(rows, dead_ids={"a"})] \
        == ["c"]
    assert [r["node_id"] for r in
            live_decode_rows(rows, include_draining=True)] == ["a", "b", "c"]


def test_least_loaded_and_hot_rows():
    rows = [_row("b", 1), _row("a", 1), _row("c", 7)]
    assert least_loaded(rows)["node_id"] == "a"  # node-id tiebreak
    assert least_loaded([]) is None
    assert [r["node_id"] for r in hot_rows(rows, 2.0)] == ["c"]  # mean 3
    assert hot_rows([_row("a", 9)], 1.0) == []       # nowhere to move work
    assert hot_rows([_row("a"), _row("b")], 1.0) == []  # idle pool


def test_directory_draining_flag_round_trips():
    d = BlockDirectory(default_ttl=5.0)
    assert d.register("n1", 0, 1, "decode.n1", role="decode", epoch=1)
    assert d.heartbeat("n1", load=2, epoch=1, draining=True)
    (row,) = d.alive()
    assert row.draining and row.load == 2
    assert live_decode_rows([{
        "node_id": row.node_id, "role": row.role, "load": row.load,
        "draining": row.draining,
    }]) == []
    assert d.heartbeat("n1", load=2, epoch=1)  # drain flag is per-beat
    assert not d.alive()[0].draining


# -- page shipping ------------------------------------------------------------


def test_spill_peek_is_non_consuming():
    arena = HostSpillArena(max_bytes=1 << 20)
    tiles = {"k": np.ones((2, 2), np.float32)}
    assert arena.put(b"key", tiles)
    got = arena.peek(b"key")
    assert got is not None and np.array_equal(got["k"], tiles["k"])
    assert len(arena) == 1 and arena.peek(b"key") is not None  # still there
    assert arena.peek(b"missing") is None


def test_prefix_pages_ship_round_trip_and_greedy_parity():
    prompt = [(i * 13) % 96 + 2 for i in range(24)]  # 3 full pages at ps=8
    opts = SamplingOptions(temperature=0.0, **OPTS)
    src = make_engine(prefix=True)
    base = drain_engine(src, src.submit(list(prompt), opts))
    src.collect_finished()

    ps, items = src.export_prefix_pages(prompt)
    assert ps == 8 and len(items) == 3

    frames = encode_pages("pg1", ps, items)
    items2, meta = decode_pages(frames)
    assert meta["ps"] == 8 and meta["op"] == "fleet.pages"
    assert [k for k, _ in items2] == [k for k, _ in items]
    for (_, a), (_, b) in zip(items, items2):
        assert sorted(a) == sorted(b)
        for name in a:
            assert np.array_equal(np.asarray(a[name]), np.asarray(b[name]))

    dst = make_engine(prefix=True)
    assert dst.import_prefix_pages(ps, items2) == 3
    assert dst.metrics.get_counter("fleet_pages_imported") == 3
    # Re-import is a no-op: the keys are already resident.
    assert dst.import_prefix_pages(ps, items2) == 0
    # The shipped pages serve a prefix-matching admission, and the
    # continuation equals the exporter's run token for token.
    got = drain_engine(dst, dst.submit(list(prompt), opts))
    assert got == base
    assert dst.metrics.get_counter("prefix_cached_tokens") >= 16


def test_pages_codec_rejects_truncated_payload():
    src = make_engine(prefix=True)
    gid = src.submit([(i * 7) % 96 + 2 for i in range(24)],
                     SamplingOptions(temperature=0.0, max_new_tokens=4))
    drain_engine(src, gid)
    src.collect_finished()
    ps, items = src.export_prefix_pages(
        [(i * 7) % 96 + 2 for i in range(24)])
    assert len(items) >= 2
    # A payload whose chain names a page that shipped no tiles must be
    # rejected, not silently installed short.
    frames = encode_pages("pg2", ps, [items[0], (items[1][0], {})])
    with pytest.raises(ValueError, match="missing page"):
        decode_pages(frames)


# -- gateway placement probe --------------------------------------------------


class _FakeDirectory:
    def __init__(self, match, rows):
        self._match, self._rows = match, rows

    def match_prefix(self, prompt):
        return self._match

    def alive(self):
        return self._rows


def _backend(fleet_cfg):
    return FleetBackend(0, prefix_cfg=PrefixConfig(min_shared_tokens=8),
                        fleet_cfg=fleet_cfg)


def test_place_cost_holder_cheapest_is_plain_prefix_routing():
    b = _backend(FleetConfig())
    rows = [_row("h", 1), _row("x", 1)]
    node = b._place_cost(_FakeDirectory(("h", 16), rows), None, [1] * 16, ())
    assert node["node_id"] == "h"
    assert b.metrics.get_counter("routed_by_prefix") == 1
    assert b.metrics.get_counter("fleet_query_moved") == 0  # no decision


def test_place_cost_arbitrates_when_holder_is_hot():
    # Dear queueing + cheap prefill: the decision is migrate -> the
    # request lands on the idle alternative, counter tallies.
    b = _backend(FleetConfig(queue_s_per_load=10.0, prefill_s_per_token=1e-9,
                             wire_bytes_per_s=1.0))
    rows = [_row("h", 5), _row("x", 0)]
    node = b._place_cost(_FakeDirectory(("h", 16), rows), None, [1] * 16, ())
    assert node["node_id"] == "x"
    assert b.metrics.get_counter("fleet_migrated") == 1
    # Cheap queueing: query_move keeps it on the holder.
    b = _backend(FleetConfig(queue_s_per_load=1e-9, prefill_s_per_token=1.0,
                             wire_bytes_per_s=1.0))
    node = b._place_cost(_FakeDirectory(("h", 16), rows), None, [1] * 16, ())
    assert node["node_id"] == "h"
    assert b.metrics.get_counter("fleet_query_moved") == 1


def test_place_cost_declines_without_a_useful_match():
    b = _backend(FleetConfig())
    rows = [_row("h", 5), _row("x", 0)]
    assert b._place_cost(_FakeDirectory((None, 0), rows), None, [1], ()) \
        is None
    # Below min_shared_tokens, or the holder is locally fenced/draining.
    assert b._place_cost(_FakeDirectory(("h", 4), rows), None, [1] * 4, ()) \
        is None
    assert b._place_cost(
        _FakeDirectory(("h", 16), rows), None, [1] * 16, {"h"}) is None
    assert b._place_cost(_FakeDirectory(
        ("h", 16), [_row("h", 5, draining=True), _row("x", 0)]),
        None, [1] * 16, ()) is None


# -- controller: autoscale against directory rows -----------------------------


@needs_native
def test_autoscale_hysteresis_and_floor():
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            dc = DirectoryClient(relay.port)
            spawned = []
            ctl = FleetController(
                relay.port,
                fleet_cfg=FleetConfig(scale_out_load=1.5, scale_in_load=0.2,
                                      scale_hold_s=1.0, min_nodes=1,
                                      max_nodes=2),
                spawn=lambda: spawned.append(1),
            )
            try:
                # Empty pool is below the floor: restore immediately, no
                # hysteresis.
                assert ctl.autoscale_once(now=0.0) == "out"
                assert spawned == [1]
                assert dc.register("f1", 0, 1, "decode.f1", role="decode",
                                   epoch=1)
                assert dc.heartbeat("f1", load=4, epoch=1)
                # Overload must HOLD for scale_hold_s before scaling out.
                assert ctl.autoscale_once(now=10.0) == "hold"
                assert ctl.autoscale_once(now=10.5) == "hold"
                assert ctl.autoscale_once(now=11.1) == "out"
                assert spawned == [1, 1]
                assert ctl.metrics.get_counter("fleet_scale_out") == 2
                assert ctl.metrics.get_gauge("fleet_pool_size") == 1.0
                # A calm tick resets the clock: no thrash on a burst.
                assert dc.heartbeat("f1", load=1, epoch=1)
                assert ctl.autoscale_once(now=12.0) == "hold"
                assert dc.heartbeat("f1", load=4, epoch=1)
                assert ctl.autoscale_once(now=13.0) == "hold"  # clock restart
            finally:
                ctl.close()
                dc.close()


@needs_native
def test_autoscale_scale_in_drains_then_fences():
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            dc = DirectoryClient(relay.port)
            retired = []
            ctl = FleetController(
                relay.port,
                fleet_cfg=FleetConfig(scale_in_load=0.5, scale_hold_s=0.2,
                                      min_nodes=1, max_nodes=3,
                                      drain_timeout_s=2.0),
                retire=retired.append,
            )
            try:
                for nid in ("f1", "f2"):
                    assert dc.register(nid, 0, 1, f"decode.{nid}",
                                       role="decode", epoch=1)
                    assert dc.heartbeat(nid, load=0, epoch=1)
                assert ctl.autoscale_once(now=0.0) == "hold"  # starts clock
                # Past the hold the least-loaded node (id tiebreak -> f1)
                # is drained (no consumer: ack times out, load reads 0 so
                # the poll exits immediately) and its lease is fenced.
                assert ctl.autoscale_once(now=0.3) == "in"
                assert retired == ["f1"]
                assert ctl.metrics.get_counter("fleet_scale_in") == 1
                assert ctl.metrics.get_counter("fleet_drains") == 1
                # The fence holds: the retired epoch cannot come back.
                assert not dc.register("f1", 0, 1, "decode.f1",
                                       role="decode", epoch=1)
                assert dc.register("f1", 0, 1, "decode.f1",
                                   role="decode", epoch=2)
                # At the floor the pool never shrinks further.
                dc.fence("f1", 2)
                assert ctl.autoscale_once(now=5.0) == "hold"
                assert ctl.autoscale_once(now=9.0) == "hold"
            finally:
                ctl.close()
                dc.close()


# -- live reshapes over a real relay ------------------------------------------


@pytest.fixture
def loop():
    loop = asyncio.new_event_loop()
    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    yield loop
    loop.call_soon_threadsafe(loop.stop)
    t.join(timeout=5)


def _fleet_stream(backend, loop, prompt, opts, timeout=60.0):
    h = backend.submit(prompt, opts, deadline=time.monotonic() + timeout)

    async def _drain():
        toks, seqs, resumed = [], [], 0
        while True:
            ev = await asyncio.wait_for(h.queue.get(), timeout=timeout)
            resumed = max(resumed, ev.resumed)
            if ev.token >= 0:
                toks.append(ev.token)
                seqs.append(ev.seq)
            if ev.finished:
                return toks, seqs, ev.finish_reason, resumed

    return asyncio.run_coroutine_threadsafe(_drain(), loop).result(
        timeout=timeout + 30
    )


RECOVERY_DCFG = DisaggConfig(
    lease_ttl_s=1.0, checkpoint_interval_ticks=2, resume_max_attempts=2,
)


def _drain_when_partway(ctl, node, min_tokens, out):
    """Fire ``ctl.drain`` once ``node``'s engine has streamed at least
    ``min_tokens`` — a reshape genuinely in flight, not before."""
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        done = sum(len(s.generated)
                   for s in list(node.engine.sessions.values()))
        if done >= min_tokens:
            break
        time.sleep(0.01)
    try:
        out.update(ctl.drain(node.node_id))
    except Exception as e:  # noqa: BLE001 - surfaced by the assertions
        out["error"] = repr(e)


@needs_native
@pytest.mark.parametrize("kind,kv_quant,temp", COMBOS)
def test_drain_live_migrates_stream_byte_exact(loop, kind, kv_quant, temp):
    """The tentpole acceptance: drain a node mid-stream; the session is
    handed off live to the survivor WITHOUT a crash (no death detected,
    no lease expiry wait) and the client-visible stream is byte-exact —
    zero tokens lost, zero duplicated — across dense/paged x f32/int8."""
    prompt = [3, 5, 7, 11, 13]
    opts = SamplingOptions(temperature=temp, top_k=20 if temp else 0, **OPTS)
    e = make_engine(kind, kv_quant)
    base = drain_engine(e, e.submit(list(prompt), opts))
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            n1 = DecodeNode(relay.port, make_engine(kind, kv_quant),
                            node_id="n1", disagg_cfg=RECOVERY_DCFG, epoch=1)
            n2 = DecodeNode(relay.port, make_engine(kind, kv_quant),
                            node_id="n2", disagg_cfg=RECOVERY_DCFG, epoch=1)
            backend = FleetBackend(relay.port, disagg_cfg=RECOVERY_DCFG)
            backend.start(loop)
            ctl = FleetController(relay.port, disagg_cfg=RECOVERY_DCFG)
            summary = {}
            drainer = threading.Thread(
                target=_drain_when_partway, args=(ctl, n1, 4, summary),
                daemon=True)
            try:
                drainer.start()
                toks, seqs, reason, resumed = _fleet_stream(
                    backend, loop, prompt, opts)
                drainer.join(timeout=30.0)
                assert "error" not in summary, summary
                assert summary["sessions"] == 1 and summary["drained"]
                assert summary["floor"] >= 1
                assert toks == base and reason == "length"
                assert seqs == list(range(len(toks)))  # no dup, no gap
                assert resumed == 1
                m = backend.metrics
                assert m.get_counter("fleet_drained_sessions") == 1
                assert m.get_counter("node_deaths_detected") == 0  # live, not
                # a crash: the handoff marker re-homed the stream directly
                assert n1.engine.metrics.get_counter(
                    "fleet_handoffs_sent") == 1
                assert ctl.metrics.get_counter("fleet_drains") == 1
                alive = {r["node_id"] for r in ctl._directory.alive()}
                assert "n1" not in alive and "n2" in alive  # fenced out
            finally:
                ctl.close()
                backend.stop()
                n2.stop()
                n1.stop()


@needs_native
def test_drain_hands_off_active_and_waiting_sessions(loop):
    """Multi-session drain: a batch-1 node holds one ACTIVE and one
    WAITING session; drain warm-migrates the active one (checkpointed)
    and cold-reschedules the queued one — both streams land byte-exact
    on the survivor."""
    opts = SamplingOptions(temperature=0.0, **OPTS)
    prompts = [[3, 5, 7, 11, 13], [2, 4, 6, 8, 10, 12]]
    bases = []
    for p in prompts:
        e = make_engine(batch=2, decode_steps=1)
        bases.append(drain_engine(e, e.submit(list(p), opts)))
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            n1 = DecodeNode(relay.port,
                            make_engine(batch=1, decode_steps=1), node_id="n1",
                            disagg_cfg=RECOVERY_DCFG, epoch=1)
            backend = FleetBackend(relay.port, disagg_cfg=RECOVERY_DCFG)
            backend.start(loop)
            ctl = FleetController(relay.port, disagg_cfg=RECOVERY_DCFG)
            results = [None, None]

            def _stream(i):
                results[i] = _fleet_stream(backend, loop, prompts[i], opts)

            threads = [threading.Thread(target=_stream, args=(i,),
                                        daemon=True) for i in range(2)]
            n2 = None
            try:
                for t in threads:
                    t.start()  # only n1 exists: both land there, one queues
                deadline = time.monotonic() + 30.0
                while (len(n1.engine.sessions) < 2
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                assert len(n1.engine.sessions) == 2
                n2 = DecodeNode(relay.port,
                                make_engine(batch=2, decode_steps=1),
                                node_id="n2", disagg_cfg=RECOVERY_DCFG,
                                epoch=1)
                deadline = time.monotonic() + 10.0
                while (len(ctl._directory.alive()) < 2
                       and time.monotonic() < deadline):
                    time.sleep(0.05)
                summary = ctl.drain("n1")
                for t in threads:
                    t.join(timeout=60.0)
                assert summary["sessions"] == 2 and summary["drained"]
                for i, (toks, seqs, reason, _resumed) in enumerate(results):
                    assert toks == bases[i] and reason == "length"
                    assert seqs == list(range(len(toks)))
                assert backend.metrics.get_counter(
                    "fleet_drained_sessions") == 2
            finally:
                ctl.close()
                backend.stop()
                if n2 is not None:
                    n2.stop()
                n1.stop()


@needs_native
def test_rebalance_migrates_sessions_off_hot_node(loop):
    """A node holding two streams next to an idle peer is hot
    (load 2 vs pool mean 1); ``rebalance_once`` live-migrates its
    longest-running session over — both streams stay byte-exact."""
    opts = SamplingOptions(temperature=0.0, **OPTS)
    prompts = [[3, 5, 7, 11, 13], [2, 4, 6, 8, 10, 12]]
    bases = []
    for p in prompts:
        e = make_engine(decode_steps=1)
        bases.append(drain_engine(e, e.submit(list(p), opts)))
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            n1 = DecodeNode(relay.port, make_engine(decode_steps=1),
                            node_id="n1",
                            disagg_cfg=RECOVERY_DCFG, epoch=1)
            backend = FleetBackend(relay.port, disagg_cfg=RECOVERY_DCFG)
            backend.start(loop)
            ctl = FleetController(
                relay.port, disagg_cfg=RECOVERY_DCFG,
                fleet_cfg=FleetConfig(hot_load_factor=1.5,
                                      rebalance_max_sessions=1))
            results = [None, None]

            def _stream(i):
                results[i] = _fleet_stream(backend, loop, prompts[i], opts)

            threads = [threading.Thread(target=_stream, args=(i,),
                                        daemon=True) for i in range(2)]
            n2 = None
            try:
                for t in threads:
                    t.start()  # only n1 exists: both decode there
                deadline = time.monotonic() + 30.0
                while (sum(len(s.generated) for s in
                           list(n1.engine.sessions.values())) < 6
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                n2 = DecodeNode(relay.port, make_engine(decode_steps=1),
                                node_id="n2",
                                disagg_cfg=RECOVERY_DCFG, epoch=1)
                deadline = time.monotonic() + 10.0
                moved = 0
                while moved == 0 and time.monotonic() < deadline:
                    # n1's heartbeat must show load 2 with idle n2 beside
                    # it before the hot detector can fire.
                    moved = ctl.rebalance_once()
                    if moved == 0:
                        time.sleep(0.1)
                for t in threads:
                    t.join(timeout=60.0)
                assert moved >= 1
                assert ctl.metrics.get_counter(
                    "fleet_rebalance_migrations") >= 1
                for i, (toks, seqs, reason, _resumed) in enumerate(results):
                    assert toks == bases[i] and reason == "length"
                    assert seqs == list(range(len(toks)))
                assert backend.metrics.get_counter(
                    "fleet_drained_sessions") >= 1
                # Rebalance is NOT a drain: n1 keeps its lease.
                alive = {r["node_id"] for r in ctl._directory.alive()}
                assert {"n1", "n2"} <= alive
            finally:
                ctl.close()
                backend.stop()
                if n2 is not None:
                    n2.stop()
                n1.stop()


@needs_native
def test_page_ship_over_relay_installs_on_target():
    """Regression: the gateway's ``_ship_pages`` leg must parse
    ``encode_pages`` frames with the kv codec's header-only reader —
    their payload is a multi-plane record stream, and ``unpack_frame``'s
    single-array body decode raises on it. Because the ship is
    best-effort (a failed copy just means a cold prefill on the
    target), nothing downstream surfaced the breakage: this pins the
    full holder → relay → target install round trip."""
    from distributed_llm_inference_tpu.distributed.relay import RelayClient

    prompt = [(i * 13) % 96 + 2 for i in range(24)]  # 3 full pages at ps=8
    e1 = make_engine(prefix=True)
    gid = e1.submit(list(prompt), SamplingOptions(
        temperature=0.0, max_new_tokens=4))
    drain_engine(e1, gid)
    e1.collect_finished()
    assert e1.prefix_match_tokens(prompt) >= 16
    e2 = make_engine(prefix=True)
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            n1 = DecodeNode(relay.port, e1, node_id="n1",
                            disagg_cfg=RECOVERY_DCFG, epoch=1)
            n2 = DecodeNode(relay.port, e2, node_id="n2",
                            disagg_cfg=RECOVERY_DCFG, epoch=1)
            backend = FleetBackend(relay.port, disagg_cfg=RECOVERY_DCFG,
                                   prefix_cfg=PrefixConfig(min_shared_tokens=8),
                                   fleet_cfg=FleetConfig())
            client = RelayClient("127.0.0.1", relay.port)
            try:
                holder = {"node_id": "n1", "queue": "decode.n1"}
                target = {"node_id": "n2", "queue": "decode.n2"}
                assert backend._ship_pages(client, holder, target,
                                           list(prompt))
                assert backend.metrics.get_counter(
                    "fleet_page_ship_failed") == 0
                assert e2.metrics.get_counter("fleet_pages_imported") == 3
                assert e2.prefix_match_tokens(prompt) >= 16
                # Cost model learned a measured wire rate from the trip
                # (EMA moved off the config seed).
                assert (backend.cost.wire_bytes_per_s
                        != FleetConfig().wire_bytes_per_s)
            finally:
                client.close()
                backend.stop()
                n2.stop()
                n1.stop()


@needs_native
@pytest.mark.chaos
def test_crash_racing_drain_loses_no_tokens(loop):
    """The satellite regression: the draining node whole-node-crashes
    while the drain is in flight (token/checkpoint/handoff frames all
    die mid-batch). Whatever the interleaving — crash before, during,
    or after the handoff ship — the stream re-homes through crash
    recovery and stays byte-exact: zero tokens lost, zero duplicated,
    and the drain call itself still completes with a fence."""
    from distributed_llm_inference_tpu.distributed.chaos import (
        ChaosProxy,
        FaultPlan,
    )

    prompt = [3, 5, 7, 11, 13]
    opts = SamplingOptions(temperature=0.0, **OPTS)
    e = make_engine()
    base = drain_engine(e, e.submit(list(prompt), opts))

    plan = FaultPlan.from_specs(["crash:fleet.tok.*:put:after=6"], seed=7)
    with RelayServer() as relay:
        with DirectoryService(relay.port, default_ttl=5.0):
            with ChaosProxy("127.0.0.1", relay.port, plan=plan) as proxy:
                n1 = DecodeNode(proxy.port, make_engine(), node_id="n1",
                                disagg_cfg=RECOVERY_DCFG, epoch=1)
                n2 = DecodeNode(relay.port, make_engine(), node_id="n2",
                                disagg_cfg=RECOVERY_DCFG, epoch=1)
                backend = FleetBackend(relay.port, disagg_cfg=RECOVERY_DCFG)
                backend.start(loop)
                # The controller talks to the REAL relay: the drain
                # command still goes out after the proxy dies.
                ctl = FleetController(relay.port, disagg_cfg=RECOVERY_DCFG)
                summary = {}
                drainer = threading.Thread(
                    target=_drain_when_partway, args=(ctl, n1, 3, summary),
                    daemon=True)
                try:
                    drainer.start()
                    toks, seqs, reason, resumed = _fleet_stream(
                        backend, loop, prompt, opts)
                    drainer.join(timeout=30.0)
                    assert plan.injected, "crash fault never fired"
                    assert "error" not in summary, summary
                    assert summary["drained"] and summary["floor"] >= 1
                    assert toks == base and reason == "length"
                    assert seqs == list(range(len(toks)))  # no dup, no gap
                    assert resumed == 1
                    assert backend.metrics.get_counter("resume_failures") == 0
                    alive = {r["node_id"] for r in ctl._directory.alive()}
                    assert "n1" not in alive and "n2" in alive
                finally:
                    ctl.close()
                    backend.stop()
                    n2.stop()
                    n1.stop()
