"""Advisory service: cross-session batching correctness + protocol.

The load-bearing invariant mirrors the campaign engine's: batching is
*routing only*.  Concurrent sessions — interleaved round by round,
merged/deduplicated per design, optionally packed across designs into
one hetero dispatch — must produce histories and frontiers bit-identical
to solo ``FifoAdvisor.run()`` calls with the same seeds.  (Budget
accounting ``n_evals`` counts cache *misses*, so it legitimately shrinks
under cache sharing; configurations, latencies, frontiers, and
hypervolumes never change.)
"""

import asyncio
import json

import numpy as np
import pytest

from repro.core import EvalConfig, FifoAdvisor
from repro.core.campaign.router import RoundRouter
from repro.core.service import (AdvisorClient, AdvisoryService,
                                DesignRegistry, ProtocolError,
                                ProtocolHandler, SessionHandle, adapt_v1)
from repro.core.service.protocol import (E_BAD_DESIGN, E_BAD_OPTIMIZER,
                                         E_BAD_REQUEST, E_BAD_SESSION,
                                         E_OVERLOADED, E_PROTO, PROTO,
                                         SUPPORTED_PROTOS)
from repro.designs import make_design

DESIGNS = ("gemm", "FeedForward")
BUDGET = 60

#: (design, optimizer, seed) mix covering 2 designs x 2 optimizers
SESSIONS = [("gemm", "grouped_sa", 0), ("gemm", "grouped_random", 3),
            ("FeedForward", "grouped_sa", 1),
            ("FeedForward", "grouped_random", 0)]


def solo_run(design, optimizer, seed, budget=BUDGET):
    return FifoAdvisor(make_design(design)).run(optimizer, budget=budget,
                                                seed=seed)


def assert_identical(dse, ref, key=""):
    assert np.array_equal(dse.result.configs, ref.result.configs), key
    assert np.array_equal(dse.result.latency, ref.result.latency), key
    assert np.array_equal(dse.result.bram, ref.result.bram), key
    assert np.array_equal(dse.result.deadlock, ref.result.deadlock), key
    assert np.array_equal(dse.frontier_points, ref.frontier_points), key
    assert dse.hypervolume() == ref.hypervolume(), key


# --------------------------------------------------------------- batching
def test_concurrent_sessions_bit_identical_to_solo():
    """2 designs x 2 optimizers batched together == 4 solo runs."""
    with AdvisoryService() as svc:
        sids = [svc.open_session(d, optimizer=o, budget=BUDGET,
                                 seed=s).id for d, o, s in SESSIONS]
        svc.run_until_idle()
        for sid, (d, o, s) in zip(sids, SESSIONS):
            assert_identical(svc.result(sid), solo_run(d, o, s),
                             f"{d}:{o}:s{s}")


def test_forced_hetero_packing_bit_identical():
    """hetero=True packs cross-design rows into shared dispatches and
    still reproduces every solo run exactly."""
    with AdvisoryService(hetero=True,
                         config=EvalConfig(max_iters=64)) as svc:
        sids = [svc.open_session(d, optimizer=o, budget=BUDGET,
                                 seed=s).id for d, o, s in SESSIONS]
        svc.run_until_idle()
        disp = svc.batcher.router.hetero
        assert disp is not None and disp.stats.n_dispatches > 0
        # both designs share each round's dispatch: never more
        # dispatches than rounds (separate per-design dispatch would
        # need up to one per design per round)
        assert disp.stats.n_dispatches <= svc.batcher.rounds
        assert set(disp.worklists) == set(DESIGNS)
        for sid, (d, o, s) in zip(sids, SESSIONS):
            assert_identical(svc.result(sid), solo_run(d, o, s),
                             f"hetero {d}:{o}:s{s}")


def test_mid_run_cancel_keeps_prefix_and_peers_exact():
    """Cancelling one session mid-run yields its history prefix and
    leaves every other session bit-identical to its solo run."""
    with AdvisoryService() as svc:
        victim = svc.open_session("gemm", optimizer="grouped_sa",
                                  budget=400, seed=5)
        peers = [svc.open_session(d, optimizer=o, budget=BUDGET,
                                  seed=s).id for d, o, s in SESSIONS]
        for _ in range(3):
            svc.step()
        svc.cancel(victim.id)
        assert victim.state == "cancelled"
        svc.run_until_idle()

        part = svc.result(victim.id)
        n = part.result.configs.shape[0]
        assert 0 < n
        ref = solo_run("gemm", "grouped_sa", 5, budget=400)
        assert n < ref.result.configs.shape[0]
        assert np.array_equal(part.result.configs,
                              ref.result.configs[:n])
        assert np.array_equal(part.result.latency,
                              ref.result.latency[:n])
        events = victim.drain_events()
        assert events and events[-1]["event"] == "cancelled"
        # cancelled sessions never advance again
        before = victim.rounds
        svc.step()
        assert victim.rounds == before

        for sid, (d, o, s) in zip(peers, SESSIONS):
            assert_identical(svc.result(sid), solo_run(d, o, s),
                             f"peer {d}:{o}:s{s}")


def test_progress_events_stream_frontier_deltas():
    with AdvisoryService() as svc:
        sess = svc.open_session("gemm", optimizer="grouped_random",
                                budget=BUDGET, seed=0)
        svc.run_until_idle()
        events = sess.drain_events()
        assert events[-1]["event"] == "done"
        progress = [e for e in events if e["event"] == "progress"]
        assert progress, "no progress events streamed"
        hv = 0.0
        for e in progress:
            assert e["hv_delta"] == pytest.approx(
                e["hypervolume"] - hv)
            assert e["hypervolume"] >= hv   # cumulative-history frontier
            hv = e["hypervolume"]
        assert events[-1]["hypervolume"] == pytest.approx(hv)


def test_pooled_service_handles_late_and_custom_designs():
    """Worker-pool mode: a design opened after the pool exists and a
    custom Design object (which a fresh worker process could not rebuild
    by name) both reach the workers as graphs and evaluate correctly."""
    from repro.core.design import Design

    def build_design():
        d = Design("qs")
        d.fifo("a", width=32)

        @d.task("src")
        def src(ctx):
            for i in range(64):
                yield ctx.delay(1)
                yield ctx.write("a", i)

        @d.task("sink")
        def sink(ctx):
            for _ in range(64):
                yield ctx.read("a")
                yield ctx.delay(2)

        return d

    with AdvisoryService(workers=1) as svc:
        first = svc.open_session("gemm", optimizer="grouped_random",
                                 budget=40, seed=0)
        late = svc.open_session("FeedForward",
                                optimizer="grouped_random",
                                budget=40, seed=1)       # graph added
        custom = svc.open_session("qs", design_obj=build_design(),
                                  optimizer="grouped_random",
                                  budget=40, seed=2)     # shipped, too
        pool = svc.batcher.router.pool
        assert pool is not None
        assert pool._graphs["qs"] is svc.registry["qs"].graph
        svc.run_until_idle()
        assert {s.state for s in (first, late, custom)} == {"done"}
        assert_identical(svc.result(first.id),
                         solo_run("gemm", "grouped_random", 0, 40))
        assert_identical(svc.result(late.id),
                         solo_run("FeedForward", "grouped_random", 1, 40))
        solo_custom = FifoAdvisor(build_design()).run(
            "grouped_random", budget=40, seed=2)
        assert_identical(svc.result(custom.id), solo_custom)


# --------------------------------------------------------------- registry
def test_registry_traces_each_design_once():
    reg = DesignRegistry()
    a1 = reg.register("gemm")
    a2 = reg.register("gemm")
    assert a1 is a2
    assert reg.names() == ["gemm"]
    with AdvisoryService(registry=reg) as svc:
        s1 = svc.open_session("gemm", budget=20, seed=0)
        svc.run_until_idle()
        assert s1.ctx.n_evals > 0
        # a later identical session rides the shared cache entirely:
        # same trajectory, zero new simulations
        s2 = svc.open_session("gemm", budget=20, seed=0)
        assert s2.advisor is a1
        svc.run_until_idle()
        assert s2.ctx.n_evals == 0
        assert np.array_equal(s1.ctx.history()[0], s2.ctx.history()[0])
    assert reg.stats()["gemm"]["cache"]["hits"] > 0


def test_service_and_campaign_share_the_router():
    """The factoring the service rides on: one routing implementation."""
    from repro.core.campaign import Campaign, CampaignSpec
    camp = Campaign(CampaignSpec(designs=("gemm",),
                                 optimizers=("grouped_random",),
                                 budget=20))
    with AdvisoryService() as svc:
        assert type(camp.router) is type(svc.batcher.router) is RoundRouter
    camp.close()


# --------------------------------------------------------------- protocol
def test_protocol_roundtrip_and_errors():
    handler = ProtocolHandler(AdvisoryService())
    resp = handler.handle({"op": "open", "design": "gemm",
                           "optimizer": "grouped_random", "budget": 30,
                           "id": "req-1"})
    assert resp["ok"] and resp["id"] == "req-1"
    sid = resp["session"]
    assert handler.handle({"op": "status", "session": sid})[
        "state"] == "running"
    run = handler.handle({"op": "run"})
    assert run["ok"] and run["running"] == 0
    res = handler.handle({"op": "result", "session": sid})
    assert res["ok"] and res["state"] == "done"
    assert res["result"]["frontier"]
    assert res["result"]["n_evals"] > 0
    events = handler.poll_events(sid)
    assert events and events[-1]["event"] == "done"

    assert not handler.handle({"op": "nope"})["ok"]
    assert not handler.handle({"op": "open"})["ok"]
    assert not handler.handle({"op": "status", "session": "s99"})["ok"]
    bad = handler.handle({"op": "cancel", "id": 7})
    assert not bad["ok"] and bad["id"] == 7


def test_error_frames_carry_stable_codes():
    """Every failure class maps to its documented ERROR_CODES entry —
    clients branch on ``code``, never on message prose."""
    handler = ProtocolHandler(AdvisoryService())
    cases = [
        ({"op": "nope"}, E_PROTO),
        ({"op": "hello", "proto": 99}, E_PROTO),
        ({"op": "open"}, E_BAD_REQUEST),
        ({"op": "status"}, E_BAD_REQUEST),
        ({"op": "open", "design": "no_such_design"}, E_BAD_DESIGN),
        ({"op": "open", "design": "gemm",
          "optimizer": "no_such_optimizer"}, E_BAD_OPTIMIZER),
        ({"op": "status", "session": "s99"}, E_BAD_SESSION),
        ({"op": "snapshot"}, E_BAD_REQUEST),
    ]
    for msg, code in cases:
        out = handler.handle(msg)
        assert not out["ok"] and out["code"] == code, (msg, out)
        assert out["error"]            # the v1 human string is still there


def test_hello_negotiates_proto_and_advertises_ops():
    with AdvisorClient() as client:
        assert client.proto == PROTO
        for proto in SUPPORTED_PROTOS:
            hello = client.request({"op": "hello", "proto": proto})
            assert hello["proto"] == proto
            assert "release" in hello["ops"]
            assert "close" not in hello["ops"]   # v1 spelling not advertised
        with pytest.raises(ProtocolError) as err:
            client.request({"op": "hello", "proto": 3})
        assert err.value.code == E_PROTO


def test_v1_messages_round_trip_through_adapter():
    """Every v1 request — including the renamed ``close`` — must keep
    working verbatim against a v2 handler (no hello, v1 field names)."""
    assert adapt_v1({"op": "close", "session": "s0"})["op"] == "release"
    assert adapt_v1({"op": "status", "session": "s0"})["op"] == "status"
    handler = ProtocolHandler(AdvisoryService())
    opened = handler.handle({"op": "open", "design": "gemm",
                             "optimizer": "grouped_random", "budget": 20,
                             "id": "v1-1"})
    assert opened["ok"] and opened["id"] == "v1-1"
    sid = opened["session"]
    v1_ops = [{"op": "status", "session": sid},
              {"op": "step"},
              {"op": "run"},
              {"op": "result", "session": sid},
              {"op": "designs"},
              {"op": "stats"},
              {"op": "cancel", "session": sid},
              {"op": "close", "session": sid},    # v1 name for release
              {"op": "shutdown"}]
    for msg in v1_ops:
        out = handler.handle(dict(msg, id=f"v1-{msg['op']}"))
        assert out["ok"], (msg, out)
        assert out["id"] == f"v1-{msg['op']}"
    # the closed session is really gone
    assert not handler.handle({"op": "status", "session": sid})["ok"]


def test_session_handle_stream_and_context_manager():
    with AdvisorClient() as client:
        with client.open("gemm", optimizer="grouped_random",
                         budget=30, progress=True) as h:
            assert isinstance(h, SessionHandle)
            assert isinstance(h, str)          # the handle IS the sid
            assert json.dumps({"session": h})  # JSON-safe as a string
            events = list(h.stream())
            assert events and events[-1]["event"] == "done"
            assert any(e["event"] == "progress" for e in events)
            assert h.status()["state"] == "done"
            assert h.result().result.configs.shape[0] > 0
            assert h.result_json()["design"] == "gemm"
        # the with-block released the session server-side
        assert client.service.sessions == {}
        with pytest.raises(ProtocolError) as err:
            h.status()
        assert err.value.code == E_BAD_SESSION


def test_deprecated_sid_methods_still_work_and_warn():
    with AdvisorClient() as client:
        h = client.open("gemm", optimizer="grouped_random", budget=20)
        client.drive()
        sid = str(h)
        with pytest.warns(DeprecationWarning, match="status"):
            assert client.status(sid)["state"] == "done"
        with pytest.warns(DeprecationWarning, match="result"):
            assert client.result(sid).result.configs.shape[0] > 0
        with pytest.warns(DeprecationWarning, match="result_json"):
            assert client.result_json(sid)["design"] == "gemm"
        with pytest.warns(DeprecationWarning, match="release"):
            rel = client.release(sid)
        assert rel["released"] and rel["state"] == "done"


def test_overload_sheds_with_retry_after():
    """At the session cap, ``open`` fails fast with E_OVERLOADED and a
    positive retry hint; running sessions never exceed the cap and
    admission resumes after a release."""
    with AdvisorClient(max_sessions=2) as client:
        h1 = client.open("gemm", optimizer="grouped_random", budget=20)
        h2 = client.open("gemm", optimizer="grouped_random", budget=20,
                         seed=1)
        with pytest.raises(ProtocolError) as err:
            client.open("gemm", optimizer="grouped_random", budget=20,
                        seed=2)
        assert err.value.code == E_OVERLOADED
        assert err.value.extra["retry_after_s"] > 0
        assert err.value.extra["max_sessions"] == 2
        assert len(client.service.running) <= 2
        assert client.service.stats()["rejected"] == 1
        client.drive()
        assert_identical(h1.result(), solo_run("gemm", "grouped_random",
                                               0, 20))
        h1.release()
        h2.release()
        h3 = client.open("gemm", optimizer="grouped_random", budget=20,
                         seed=2)             # admission resumes
        client.drive()
        assert h3.status()["state"] == "done"


def test_release_evicts_session_and_hetero_ignores_workers():
    with AdvisorClient() as client:
        h = client.open("gemm", optimizer="grouped_random", budget=20)
        client.drive()
        assert h.result().result.configs.shape[0] > 0
        rel = h.release()
        assert rel["released"] and rel["state"] == "done"
        with pytest.raises(ProtocolError):
            h.status()     # forgotten server-side
        assert client.service.sessions == {}
    # hetero owns full-solve rows in-process: workers are normalized off
    with AdvisoryService(hetero=True, workers=4) as svc:
        assert svc.batcher.workers == 0


def test_optimizer_close_is_public_and_terminal():
    from repro.core.optimizers import OPTIMIZERS
    adv = FifoAdvisor(make_design("gemm"))
    opt = OPTIMIZERS["grouped_random"](adv.make_context(seed=0),
                                       budget=500)
    req = opt.propose()
    assert req is not None
    opt.close()
    assert opt.done and opt.propose() is None


def test_advisor_client_run_matches_solo():
    with AdvisorClient() as client:
        h = client.open("gemm", optimizer="grouped_sa", budget=BUDGET,
                        seed=2)
        client.drive()
        assert_identical(h.result(), solo_run("gemm", "grouped_sa", 2))
        payload = h.result_json()
        assert payload["design"] == "gemm"
        assert json.dumps(payload)   # JSON-ready end to end
        with pytest.raises(ProtocolError):
            client.request({"op": "result", "session": "s42"})


# ----------------------------------------------------------------- server
def test_tcp_server_round_trip():
    """Full wire path: TCP connect, open, run, events, result, shutdown."""
    from repro.launch.serve import AdvisoryServer

    async def scenario():
        server = AdvisoryServer(idle_sleep_s=0.001)
        tcp = await server.serve_tcp("127.0.0.1", 0)
        port = tcp.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)

        async def rpc(msg):
            writer.write((json.dumps(msg) + "\n").encode())
            await writer.drain()
            while True:
                frame = json.loads(await reader.readline())
                if "event" in frame:
                    frames.append(frame)
                    continue
                return frame

        frames = []
        opened = await rpc({"op": "open", "design": "gemm",
                            "optimizer": "grouped_random",
                            "budget": 40, "id": 1})
        assert opened["ok"] and opened["id"] == 1
        sid = opened["session"]
        # the background pump drives the session without explicit "run"
        for _ in range(200):
            status = await rpc({"op": "status", "session": sid})
            if status["state"] == "done":
                break
            await asyncio.sleep(0.01)
        assert status["state"] == "done"
        result = await rpc({"op": "result", "session": sid})
        assert result["ok"] and result["result"]["frontier"]
        # events were pushed while polling
        deadline = 100
        while not any(f["event"] == "done" for f in frames) and deadline:
            line = await asyncio.wait_for(reader.readline(), timeout=2)
            frames.append(json.loads(line))
            deadline -= 1
        assert any(f["event"] == "done" for f in frames)
        bye = await rpc({"op": "shutdown"})
        assert bye["ok"]
        writer.close()
        tcp.close()
        await tcp.wait_closed()
        await server.aclose()

    asyncio.run(asyncio.wait_for(scenario(), timeout=120))
