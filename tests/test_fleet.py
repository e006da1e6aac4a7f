"""Elastic fleet control plane: SLO-driven autoscaling (hysteresis,
cooldowns, bounds), dynamic router membership with bounded rendezvous key
movement, scale-down cleanup (no /healthz provider leaks, no stale breaker
evidence), and the zero-downtime deploy pipeline with automatic rollback
(inference/fleet.py + router.py add/remove/restart_replica).

Fast tests drive fleets of STATIC fake-model engines (the test_router.py
pattern) so the control plane is exercised without JAX compiles; the
real-engine 4x-traffic-step-during-rollout drill with an injected
preemption runs behind the chaos/slow markers (tools/run_chaos.sh). The
invariants: every submitted future resolves completed-or-typed, a scale
decision needs a SUSTAINED signal, and a failed deploy always ends with
every replica serving the previous version.
"""

import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from paddlepaddle_tpu.inference import (
    DeployError,
    FleetController,
    FleetPolicy,
    ServingEngine,
    ServingError,
    ServingRouter,
)
from paddlepaddle_tpu.inference.fleet import decide
from test_serving_robustness import FakeModel, _prompt

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_QUIET = 60.0     # prober quiet; tests drive probes/ticks explicitly


def _policy(**kw):
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("up_streak", 2)
    kw.setdefault("down_streak", 3)
    kw.setdefault("cooldown_up_s", 0.0)
    kw.setdefault("cooldown_down_s", 0.0)
    kw.setdefault("health_timeout_s", 5.0)
    kw.setdefault("drain_timeout_s", 2.0)
    return FleetPolicy(**kw)


def _factory(model_fn=None):
    """Versioned engine factory over instant fake models; ``model_fn``
    maps the version label to a model (the deploy tests' seam)."""

    def factory(version):
        model = model_fn(version) if model_fn is not None else FakeModel()
        return ServingEngine(model, mode="static", max_batch_size=4,
                             max_wait_ms=2.0, max_len=64)

    return factory


def _fleet(n=1, model_fn=None, policy=None, **kw):
    fc = FleetController(_factory(model_fn), initial_replicas=n,
                         policy=policy or _policy(),
                         probe_interval_s=_QUIET, **kw)
    fc.start(autoscaler=False)
    fc.router._probe_once()
    return fc


def _force_signal(fc, est_wait, queue_depth=0):
    for rep in fc.router._replicas:
        rep.snapshot = dict(rep.snapshot or {}, ok=True,
                            est_wait_s=est_wait, queue_depth=queue_depth)


def _mk_bundle(tmp, name, corrupt=False):
    """A manifest-only candidate bundle: enough for the deploy pipeline's
    stdlib validation (real AOT payload round-trips are pinned by
    tests/test_compile_plan.py in fresh subprocesses)."""
    bp = os.path.join(str(tmp), name)
    os.makedirs(bp, exist_ok=True)
    manifest = {"format_version": 1, "created_unix": time.time(),
                "version": f"{name}-vid", "fingerprint": "f" * 64,
                "entries": []}
    if corrupt:
        with open(os.path.join(bp, "decode.xc"), "wb") as f:
            f.write(b"junk")
        manifest["entries"] = [{"key": "decode", "file": "decode.xc",
                                "bytes": 4, "sha256": "0" * 64}]
    with open(os.path.join(bp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    return bp


def _resolve_all(futs, timeout=60):
    oks, errs = [], []
    for f in futs:
        try:
            oks.append(f.result(timeout))
        except Exception as e:  # noqa: BLE001 — collected for assertions
            errs.append(e)
    return oks, errs


# -- policy ------------------------------------------------------------------

def test_decide_hysteresis_cooldowns_and_bounds():
    pol = _policy(min_replicas=1, max_replicas=3, up_streak=2,
                  down_streak=3, cooldown_up_s=10.0, cooldown_down_s=20.0)
    state = {"hot": 0, "idle": 0, "last_action_t": None}
    hot = {"replicas": 1, "healthy": 1, "est_wait_max": 5.0,
           "queue_depth": 4, "burn": None}
    # one hot reading is NOT a decision (hysteresis)
    action, reason = decide(pol, hot, state, now=100.0)
    assert action is None and "streak 1/2" in reason
    action, reason = decide(pol, hot, state, now=101.0)
    assert action == "up" and "est_wait" in reason
    # burn beats est-wait as the named reason
    burn_sig = dict(hot, est_wait_max=0.0, burn=3.0)
    state2 = {"hot": 1, "idle": 0, "last_action_t": None}
    action, reason = decide(pol, burn_sig, state2, now=0.0)
    assert action == "up" and "slo_burn" in reason
    # cooldown blocks, streak keeps accumulating
    state3 = {"hot": 5, "idle": 0, "last_action_t": 99.0}
    action, reason = decide(pol, hot, state3, now=100.0)
    assert action is None and "cooldown" in reason
    action, _ = decide(pol, hot, state3, now=200.0)
    assert action == "up"
    # max bound refuses even a sustained violation
    at_max = dict(hot, replicas=3)
    action, reason = decide(pol, at_max, {"hot": 9, "idle": 0,
                                          "last_action_t": None}, 0.0)
    assert action is None and "max_replicas" in reason
    # idle needs its own (longer) streak, then scales down
    idle = {"replicas": 2, "healthy": 2, "est_wait_max": 0.0,
            "queue_depth": 0, "burn": 0.0}
    state4 = {"hot": 0, "idle": 0, "last_action_t": None}
    for i in range(2):
        action, _ = decide(pol, idle, state4, now=float(i))
        assert action is None
    action, reason = decide(pol, idle, state4, now=3.0)
    assert action == "down" and "idle" in reason
    # min bound refuses
    at_min = dict(idle, replicas=1)
    action, reason = decide(pol, at_min, {"hot": 0, "idle": 9,
                                          "last_action_t": None}, 0.0)
    assert action is None and "min_replicas" in reason
    # a queue that is backed up but not over the est-wait bound resets
    # BOTH streaks (neither hot nor idle)
    mid = {"replicas": 2, "healthy": 2, "est_wait_max": 0.5,
           "queue_depth": 3, "burn": None}
    state5 = {"hot": 1, "idle": 2, "last_action_t": None}
    action, reason = decide(pol, mid, state5, now=0.0)
    assert action is None and state5["hot"] == 0 and state5["idle"] == 0


# -- router membership -------------------------------------------------------

def test_add_remove_replica_bounded_rendezvous_movement():
    """Joining a replica moves ONLY the prefix keys it now owns; leaving
    returns exactly those keys to their previous homes — the property
    that keeps the fleet-wide prompt-cache hit rate through scaling."""
    r = ServingRouter([lambda: ServingEngine(FakeModel(), mode="static",
                                             max_batch_size=4, max_len=64)
                       for _ in range(3)], probe_interval_s=_QUIET)
    r.start()
    try:
        r._probe_once()
        rng = np.random.default_rng(0)
        prefixes = [rng.integers(0, 1000, (16,)).astype(np.int32)
                    for _ in range(24)]

        def route(p):
            class _P:
                tried = set()
                prefix_key = p.tobytes()

            return r._pick(_P()).name

        before = {p.tobytes(): route(p) for p in prefixes}
        name = r.add_replica(lambda: ServingEngine(
            FakeModel(), mode="static", max_batch_size=4, max_len=64))
        r._probe_once()
        assert name == "r3" and len(r._replicas) == 4
        after = {p.tobytes(): route(p) for p in prefixes}
        moved = {k for k in before if after[k] != before[k]}
        assert all(after[k] == "r3" for k in moved), \
            "keys may move ONLY onto the joining replica"
        assert moved, "24 prefixes over 4 replicas should give r3 some keys"
        # the new replica actually serves routed traffic
        assert r.submit(_prompt(), max_new_tokens=2).result(30).shape == (6,)
        # duplicate names are refused
        with pytest.raises(ValueError):
            r.add_replica(lambda: ServingEngine(
                FakeModel(), mode="static", max_batch_size=4, max_len=64),
                name="r1")
        # leaving: exactly the owned keys return to their old homes
        res = r.remove_replica("r3")
        assert res["replica"] == "r3" and len(r._replicas) == 3
        restored = {p.tobytes(): route(p) for p in prefixes}
        assert restored == before
        assert r.stats["replicas_added"] == 1
        assert r.stats["replicas_removed"] == 1
    finally:
        r.stop()


def test_remove_replica_is_deliberate_and_refuses_last():
    r = ServingRouter([lambda: ServingEngine(
        FakeModel(delay_s=0.02), mode="static", max_batch_size=1,
        max_len=64) for _ in range(2)], probe_interval_s=_QUIET)
    try:
        futs = [r.submit(_prompt(), max_new_tokens=2) for _ in range(6)]
        res = r.remove_replica("r1", drain_timeout=5.0)
        oks, errs = _resolve_all(futs)
        # zero dropped: drain sheds failed over to the surviving replica
        assert len(oks) == 6 and not errs, \
            [f"{type(e).__name__}: {e}" for e in errs]
        assert res["clean"] is True
        # deliberate: no eviction was recorded, no breaker opened
        assert r.stats["evictions"] == 0
        assert res["breaker"] == "closed"
        # the removed engine is really stopped (its loop thread is gone)
        assert "r1" not in [rep.name for rep in r._replicas]
        with pytest.raises(ValueError):
            r.remove_replica("r0")
        with pytest.raises(KeyError):
            r.remove_replica("r7")
    finally:
        r.stop()


# -- autoscaler --------------------------------------------------------------

def test_scale_up_on_sustained_violation_with_cooldown_and_max():
    pol = _policy(max_replicas=3, up_streak=2, cooldown_up_s=30.0)
    fc = _fleet(1, policy=pol)
    try:
        _force_signal(fc, est_wait=5.0)
        assert fc._tick()["action"] is None        # streak 1: hysteresis
        assert len(fc.router._replicas) == 1
        assert fc._tick()["action"] == "up"        # streak 2: scale
        assert len(fc.router._replicas) == 2
        assert fc.stats["scale_ups"] == 1
        assert fc.last_scaleup_to_healthy_s is not None
        assert fc.health()["fleet"]["replicas_target"] == 2
        # the new replica serves routed traffic immediately (pre-warmed +
        # probed before it entered the pick set)
        fc.router._probe_once()
        assert fc.generate(_prompt(), max_new_tokens=2,
                           timeout=30).shape == (6,)
        # cooldown: the violation persists but no second scale fires
        _force_signal(fc, est_wait=5.0)
        for _ in range(4):
            fc._tick()
        assert len(fc.router._replicas) == 2
        # cooldown elapsed (rewound, not slept) -> next sustained
        # violation adds the third; max_replicas then caps the fleet
        fc._state["last_action_t"] -= 60.0
        _force_signal(fc, est_wait=5.0)
        for _ in range(3):
            fc._tick()
        assert len(fc.router._replicas) == 3
        fc._state["last_action_t"] -= 60.0
        _force_signal(fc, est_wait=5.0)
        for _ in range(3):
            assert fc._tick()["action"] is None
        assert len(fc.router._replicas) == 3      # hard max bound
    finally:
        fc.stop()


def test_scale_down_idle_by_deliberate_drain():
    pol = _policy(min_replicas=1, down_streak=3, cooldown_down_s=0.0)
    fc = _fleet(3, policy=pol)
    try:
        _force_signal(fc, est_wait=0.0, queue_depth=0)
        for _ in range(2):
            assert fc._tick()["action"] is None
        assert fc._tick()["action"] == "down"
        assert len(fc.router._replicas) == 2
        assert fc.stats["scale_downs"] == 1
        # deliberate: the drain produced no breaker/eviction evidence
        assert fc.router.stats["evictions"] == 0
        # down to min, then the bound holds
        _force_signal(fc, est_wait=0.0)
        for _ in range(3):
            fc._tick()
        assert len(fc.router._replicas) == 1
        _force_signal(fc, est_wait=0.0)
        for _ in range(4):
            assert fc._tick()["action"] is None
        assert len(fc.router._replicas) == 1      # hard min bound
        fc.router._probe_once()
        assert fc.generate(_prompt(), max_new_tokens=2,
                           timeout=30).shape == (6,)
    finally:
        fc.stop()


def test_scale_down_bounds_in_rotation_capacity_not_census():
    """min_replicas bounds SERVING capacity: with a deploy's canary out
    of rotation, an idle streak must not drain the replica actually
    carrying the traffic (found by an e2e drive where a mid-deploy
    scale-down left the fleet with zero in-rotation replicas)."""
    fc = _fleet(2, policy=_policy(min_replicas=1, down_streak=1))
    try:
        fc.router._replicas[0].in_rotation = False   # canary out
        _force_signal(fc, est_wait=0.0, queue_depth=0)
        for _ in range(3):
            assert fc._tick()["action"] != "down" or \
                len(fc.router._replicas) == 2
        assert len(fc.router._replicas) == 2
        assert fc.stats["scale_downs"] == 0
        # canary readmitted -> the idle streak may drain again
        fc.router._replicas[0].in_rotation = True
        _force_signal(fc, est_wait=0.0, queue_depth=0)
        fc._tick()
        assert len(fc.router._replicas) == 1
    finally:
        fc.stop()


def test_scale_cycle_no_provider_leaks_no_stale_breaker():
    """The satellite fix pin: scale-up -> scale-down -> scale-up leaves no
    orphaned /healthz provider and no stale breaker evidence — a removed
    replica's engine unregisters itself, and the router drops its breaker
    with it, so a later replica starts with a clean slate."""
    from paddlepaddle_tpu.observability import exporter as _exporter

    e = _exporter.start(port=0)
    fc = None
    try:
        fc = _fleet(1, policy=_policy(max_replicas=3))
        baseline = len(e._health_providers)   # router + fleet + 1 serving
        serving_n = sum(1 for n in e._health_providers if "serving" in n)
        assert serving_n == 1
        for cycle in range(2):
            _force_signal(fc, est_wait=5.0)
            for _ in range(2):
                fc._tick()
            assert len(fc.router._replicas) == 2
            assert sum(1 for n in e._health_providers
                       if "serving" in n) == 2
            # poison the breaker history of the replica scale-down will
            # pick (least loaded, name-ordered tiebreak): its evidence
            # must leave WITH it
            victim = min(fc.router._replicas,
                         key=lambda r: (r.inflight, r.name))
            victim.breaker.record_failure()
            victim.breaker.record_failure()
            _force_signal(fc, est_wait=0.0)
            for _ in range(3):
                fc._tick()
            assert len(fc.router._replicas) == 1, f"cycle {cycle}"
            # no provider leak: the removed engine unregistered itself
            assert len(e._health_providers) == baseline, \
                sorted(e._health_providers)
        # every surviving replica's breaker is clean (no stale evidence
        # from any removed replica's poisoned history)
        for rep in fc.router._replicas:
            assert rep.breaker.consecutive_failures == 0
            assert rep.breaker.state == "closed"
        fc.router._probe_once()
        assert fc.generate(_prompt(), max_new_tokens=2,
                           timeout=30).shape == (6,)
    finally:
        if fc is not None:
            fc.stop()
        _exporter.stop()


def test_autoscaler_thread_closes_the_loop():
    """The loop form: a sustained synthetic violation scales the fleet
    without anyone calling _tick()."""
    pol = _policy(max_replicas=2, up_streak=2)
    pol.interval_s = 0.02
    fc = FleetController(_factory(), initial_replicas=1, policy=pol,
                         probe_interval_s=_QUIET)
    fc.start()                  # autoscaler thread on
    try:
        fc.router._probe_once()
        deadline = time.time() + 5.0
        while time.time() < deadline and len(fc.router._replicas) < 2:
            _force_signal(fc, est_wait=5.0)   # keep the signal hot (new
            time.sleep(0.02)                  # replicas join idle)
        assert len(fc.router._replicas) == 2
        assert fc.health()["fleet"]["autoscaler"]["running"]
    finally:
        fc.stop()
    assert not fc.health()["fleet"]["autoscaler"]["running"]


# -- deploy pipeline ---------------------------------------------------------

def test_deploy_promotes_under_traffic_with_zero_drops(tmp_path):
    v2 = _mk_bundle(tmp_path, "v2")
    fc = _fleet(3, policy=_policy(), retry_policy=None)
    futs, stop = [], threading.Event()
    lock = threading.Lock()

    def client():
        while not stop.is_set():
            try:
                f = fc.submit(_prompt(), max_new_tokens=2)
            except ServingError:
                continue
            with lock:
                futs.append(f)
            time.sleep(0.002)

    threads = [threading.Thread(target=client) for _ in range(2)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.05)
        res = fc.deploy(v2, canary_requests=3, canary_new_tokens=2)
        stop.set()
        for t in threads:
            t.join(30)
        assert res["ok"], res
        assert res["stage"] == "done"
        assert res["version"] == v2 and res["previous"] is None
        assert res["manifest_version"] == "v2-vid"
        assert res["canary"]["completed"] == 3
        # every replica serves the candidate, through a fresh engine
        assert set(fc._versions.values()) == {v2}
        assert all(rep.client.generation >= 1
                   for rep in fc.router._replicas)
        assert fc.version == v2 and fc.previous_version is None
        assert fc.rollout["state"] == "done"
        assert fc.stats["rollouts"] == 1 and fc.stats["rollbacks"] == 0
        # zero dropped requests across the whole rollout
        with lock:
            taken = list(futs)
        assert len(taken) > 10
        oks, errs = _resolve_all(taken)
        assert not errs, [f"{type(e).__name__}: {e}" for e in errs[:5]]
        fc.router._probe_once()
        h = fc.health()
        assert h["ok"] and h["fleet"]["version"] == v2
    finally:
        stop.set()
        for t in threads:
            t.join(5)
        fc.stop()


def test_deploy_rejects_bad_bundles_before_touching_the_fleet(tmp_path):
    fc = _fleet(2)
    try:
        gens = [rep.client.generation for rep in fc.router._replicas]
        with pytest.raises(DeployError) as ei:
            fc.deploy(os.path.join(str(tmp_path), "missing"))
        assert ei.value.stage == "validate"
        corrupt = _mk_bundle(tmp_path, "bad", corrupt=True)
        with pytest.raises(DeployError) as ei:
            fc.deploy(corrupt)
        assert ei.value.stage == "validate"
        assert "sha256" in str(ei.value)
        # the fleet was never touched: no restarts, no version change
        assert [rep.client.generation
                for rep in fc.router._replicas] == gens
        assert fc.version is None and fc.rollout["state"] == "idle"
        assert isinstance(ei.value, ServingError)
    finally:
        fc.stop()


def test_deploy_canary_gate_failure_rolls_back(tmp_path):
    """A candidate whose canary requests fail never reaches a second
    replica; the canary is restored to the previous version and the
    fleet keeps serving."""
    bad = _mk_bundle(tmp_path, "bad")

    def model_fn(version):
        return FakeModel(fail_next=10 ** 6) if version == bad \
            else FakeModel()

    fc = _fleet(2, model_fn=model_fn)
    try:
        res = fc.deploy(bad, canary_requests=2, canary_new_tokens=2,
                        canary_timeout=30)
        assert not res["ok"] and res["stage"] == "canary"
        assert "canary requests failed" in res["reasons"][0]
        # rolled back: everyone on the previous version, fleet healthy
        assert set(fc._versions.values()) == {None}
        assert fc.version is None
        assert fc.rollout["state"] == "rolled_back"
        assert fc.rollout["reasons"] == res["reasons"]
        assert fc.stats["rollbacks"] == 1 and fc.stats["rollouts"] == 0
        fc.router._probe_once()
        assert fc.health()["ok"]
        oks, errs = _resolve_all(
            [fc.submit(_prompt(), max_new_tokens=2) for _ in range(4)])
        assert len(oks) == 4 and not errs
        # a canary that never turns HEALTHY rolls back the same way
        # (health-gate failure, not probe failure). A tripped breaker is
        # the persistent not-ok state: start() deliberately does NOT
        # clear it (only the drain->start cycle resets failure history)
        dead = _mk_bundle(tmp_path, "dead")
        orig = fc.factory

        def factory(version):
            eng = orig(version)
            if version == dead:
                eng._breaker.trip()
            return eng

        fc.factory = factory
        fc.policy.health_timeout_s = 0.4
        res = fc.deploy(dead, canary_requests=1)
        assert not res["ok"] and res["stage"] == "canary"
        assert "never turned healthy" in res["reasons"][0]
        assert set(fc._versions.values()) == {None}
        fc.router._probe_once()
        assert fc.health()["ok"]
    finally:
        fc.stop()


def test_deploy_midrollout_regression_rolls_back_every_replica(tmp_path):
    """The acceptance pin: the canary passes, then a LATER replica fails
    its health gate on the candidate mid-rollout — the pipeline
    automatically restores the previous bundle on every updated replica
    (canary included) and the fleet ends the rollout serving the previous
    version everywhere."""
    v2 = _mk_bundle(tmp_path, "v2")
    builds = {"n": 0}

    def model_fn(version):
        return FakeModel()

    fc = _fleet(3, model_fn=model_fn,
                policy=_policy(health_timeout_s=0.4))
    orig = fc.factory

    def factory(version):
        eng = orig(version)
        if version == v2:
            builds["n"] += 1
            if builds["n"] >= 2:      # canary passes; replica #2 is sick
                eng._breaker.trip()   # persistently not-ok (start() does
                #   not clear a tripped breaker)
        return eng

    fc.factory = factory
    try:
        res = fc.deploy(v2, canary_requests=2, canary_new_tokens=2)
        assert not res["ok"] and res["stage"] == "rollout"
        assert "failed its health gate" in res["reasons"][0]
        assert res["version"] is None        # still the previous version
        # EVERY replica — canary included — ends on the previous version
        assert set(fc._versions.values()) == {None}
        assert fc.rollout["state"] == "rolled_back"
        assert fc.stats["rollbacks"] == 1
        fc.router._probe_once()
        h = fc.health()
        assert h["ok"] and h["router"]["healthy"] == 3
        oks, errs = _resolve_all(
            [fc.submit(_prompt(), max_new_tokens=2) for _ in range(6)])
        assert len(oks) == 6 and not errs
        # the fleet can still promote a GOOD candidate afterwards
        v3 = _mk_bundle(tmp_path, "v3")
        res = fc.deploy(v3, canary_requests=2, canary_new_tokens=2)
        assert res["ok"] and set(fc._versions.values()) == {v3}
    finally:
        fc.stop()


def test_deploy_burn_bar_inherits_preexisting_burn(tmp_path):
    """Burn already in the sliding window at deploy start (a pre-deploy
    traffic spike) is NOT attributed to the candidate: the rollback bar
    inherits it, and only burn pushed PAST it triggers rollback (found
    by an e2e drive where a good candidate was rolled back for a burst
    that preceded the deploy)."""
    v2 = _mk_bundle(tmp_path, "v2")
    fc = _fleet(2)
    orig = fc.read_signal
    try:
        # the window reports burn 50 throughout — stale spike, flat
        fc.read_signal = lambda: dict(orig(), burn=50.0)
        res = fc.deploy(v2, canary_requests=2, canary_new_tokens=2)
        assert res["ok"], res["reasons"]
        assert set(fc._versions.values()) == {v2}
        # ...but burn GROWING past the inherited bar still rolls back
        v3 = _mk_bundle(tmp_path, "v3")
        burns = iter([50.0] + [80.0] * 10)   # first read = deploy start
        fc.read_signal = lambda: dict(orig(), burn=next(burns))
        res = fc.deploy(v3, canary_requests=2, canary_new_tokens=2)
        assert not res["ok"] and res["stage"] == "rollout"
        assert "rollback bar 50" in res["reasons"][0]
        assert set(fc._versions.values()) == {v2}
    finally:
        fc.read_signal = orig
        fc.stop()


# -- observability + renderers -----------------------------------------------

def test_fleet_metrics_flight_events_and_journey_spans(tmp_path):
    import paddlepaddle_tpu.observability as obs
    from paddlepaddle_tpu.observability import flight, reqtrace

    obs.reset()
    obs.enable(trace=False, metrics=True, watchdog_=False)
    flight.enable(capacity=256)
    reqtrace.enable()
    fc = None
    try:
        fc = _fleet(1, policy=_policy(max_replicas=2))
        _force_signal(fc, est_wait=5.0)
        for _ in range(2):
            fc._tick()
        _force_signal(fc, est_wait=0.0)
        for _ in range(3):
            fc._tick()
        v2 = _mk_bundle(tmp_path, "v2")
        res = fc.deploy(v2, canary_requests=1, canary_new_tokens=2)
        assert res["ok"]
        snap = obs.snapshot()
        assert sum(snap.get("paddle_fleet_scale_ups_total", {})
                   .values()) == 1
        assert sum(snap.get("paddle_fleet_scale_downs_total", {})
                   .values()) == 1
        assert sum(snap.get("paddle_fleet_rollouts_total", {})
                   .values()) == 1
        assert snap["paddle_fleet_replicas"][()] == 1
        assert snap["paddle_fleet_replicas_target"][()] == 1
        assert snap["paddle_fleet_scaleup_to_healthy_seconds"][()] >= 0
        text = obs.to_prometheus_text()
        assert "paddle_fleet_replicas" in text
        assert "paddle_fleet_scale_ups_total" in text
        events = [e for e in flight.get().events()
                  if e.get("kind") == "fleet"]
        kinds = {(e.get("data") or {}).get("event") for e in events}
        assert {"scale_up", "scale_down", "begin", "done"} <= kinds
        # fleet.scale / fleet.rollout spans land in the journey ring
        spans = [sp.get("name") for j in reqtrace.journeys()
                 for sp in j.spans]
        assert "fleet.scale" in spans and "fleet.rollout" in spans
    finally:
        if fc is not None:
            fc.stop()
        reqtrace.disable()
        flight.disable()
        obs.disable()
        obs.reset()


def test_obsctl_fleet_renders_the_block(capsys):
    from paddlepaddle_tpu.observability import exporter as _exporter

    spec = importlib.util.spec_from_file_location(
        "obsctl", os.path.join(_REPO, "tools", "obsctl.py"))
    obsctl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obsctl)
    e = _exporter.start(port=0)
    fc = None
    try:
        fc = _fleet(2)
        fc._tick()
        target = f"127.0.0.1:{e.port}"
        assert obsctl.main(["fleet", target]) == 0
        out = capsys.readouterr().out
        assert "replicas=2/target 2" in out
        assert "autoscaler: stopped" in out
        assert "rollout: idle" in out
        assert "last decision:" in out
        assert "r0" in out and "r1" in out
        assert obsctl.main(["fleet", target, "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["fleet"]["replicas"] == 2
        # no fleet provider -> one stderr line, rc 1
        fc.stop()
        fc = None
        assert obsctl.main(["fleet", target]) == 1
        assert "no fleet provider" in capsys.readouterr().err
    finally:
        if fc is not None:
            fc.stop()
        _exporter.stop()


def test_drain_reason_labels_deliberate_scale_down():
    import paddlepaddle_tpu.observability as obs

    obs.reset()
    obs.enable(trace=False, metrics=True, watchdog_=False)
    eng = ServingEngine(FakeModel(delay_s=0.05), mode="static",
                        max_batch_size=1, max_len=64)
    try:
        futs = [eng.submit(_prompt(), max_new_tokens=2) for _ in range(4)]
        eng.drain(0.01, reason="scale_down")
        _resolve_all(futs, timeout=10)
        snap = obs.snapshot()
        shed = snap.get("paddle_serving_shed_total", {})
        assert sum(v for k, v in shed.items()
                   if dict(k).get("reason") == "scale_down") > 0
        drains = snap.get("paddle_serving_drains_total", {})
        assert any(dict(k).get("reason") == "scale_down"
                   for k in drains)
    finally:
        obs.disable()
        obs.reset()
        eng.stop()


# -- open-loop traffic + perf gate -------------------------------------------

def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_open_loop_traffic_helpers():
    sb = _load_tool("serving_bench")
    tr = sb.parse_traffic("step:4@5")
    assert tr == {"kind": "step", "mult": 4.0, "at_s": 5.0}
    rng = np.random.default_rng(0)
    offs = sb.arrival_offsets(tr, 2.0, 40, rng)
    assert offs == sorted(offs)
    pre = [b - a for a, b in zip(offs, offs[1:]) if b < 5.0]
    post = [b - a for a, b in zip(offs, offs[1:]) if a >= 5.0]
    assert all(abs(g - 0.5) < 1e-9 for g in pre)      # base rate 2/s
    assert all(abs(g - 0.125) < 1e-9 for g in post)   # 4x after the step
    po = sb.parse_traffic("poisson:8")
    offs = sb.arrival_offsets(po, 2.0, 4000, rng)
    assert abs(offs[-1] / 4000 - 0.125) < 0.02        # mean gap 1/rate
    for bad in ("step:4", "burst:2@1", "step:x@1", "poisson:zz"):
        with pytest.raises(ValueError):
            sb.parse_traffic(bad)
    # summary: drops counted, post-step p99 isolates the step window
    recs = [
        {"t_submit": 0.5, "outcome": "ok", "ttft_s": 0.05, "tokens": 8,
         "t_done": 0.9},
        {"t_submit": 5.5, "outcome": "ok", "ttft_s": 0.30, "tokens": 8,
         "t_done": 6.2},
        {"t_submit": 5.8, "outcome": "refused", "error": "X"},
        {"t_submit": 6.1, "outcome": "failed", "error": "Y"},
    ]
    s = sb.traffic_summary(recs, tr)
    assert s["dropped_requests"] == 2
    assert s["submitted"] == 4 and s["completed"] == 2
    assert s["step_ttft_p99_ms"] == 300.0     # only the post-step request
    assert s["ttft_p99_ms"] == 300.0
    w0 = next(w for w in s["windows"] if w["t_s"] == 0.0)
    assert w0["submitted"] == 1 and w0["completed"] == 1
    assert w0["tok_s"] == 8.0
    w5 = next(w for w in s["windows"] if w["t_s"] == 5.0)
    assert w5["submitted"] == 2 and w5["dropped"] == 1
    w6 = next(w for w in s["windows"] if w["t_s"] == 6.0)
    assert w6["dropped"] == 1 and w6["completed"] == 1


def test_perf_gate_fleet_fields(tmp_path):
    pg = _load_tool("perf_gate")
    base = {"serving_bench": {"traffic": {
        "step_ttft_p99_ms": 100.0, "dropped_requests": 0,
        "scaleup_to_healthy_s": 2.0}}}

    def rec(path, doc):
        p = os.path.join(str(tmp_path), path)
        with open(p, "w") as f:
            json.dump(doc, f)
        return p

    b = rec("base.json", base)
    # a driver-format bench record for the gate's required --baseline
    bench = rec("bench.json", {"n": 5, "rc": 0, "parsed": {
        "metric": "llama_train_tokens_per_sec_per_chip", "value": 1000.0,
        "detail": {"mfu": 0.5, "configs": {}}}})
    assert pg.main(["--baseline", bench, "--serving", b, b]) == 0
    # post-step TTFT regression past the latency budget fails
    worse = rec("ttft.json", {"serving_bench": {"traffic": {
        "step_ttft_p99_ms": 400.0, "dropped_requests": 0,
        "scaleup_to_healthy_s": 2.0}}})
    assert pg.main(["--baseline", bench, "--serving", worse, b]) == 1
    # dropped_requests is a HARD zero floor: 0 -> 1 fails regardless of
    # any relative budget
    dropped = rec("drop.json", {"serving_bench": {"traffic": {
        "step_ttft_p99_ms": 100.0, "dropped_requests": 1,
        "scaleup_to_healthy_s": 2.0}}})
    assert pg.main(["--baseline", bench, "--serving", dropped, b]) == 1
    # a slower scale-up (bundle arming broken) fails
    slow = rec("slow.json", {"serving_bench": {"traffic": {
        "step_ttft_p99_ms": 100.0, "dropped_requests": 0,
        "scaleup_to_healthy_s": 20.0}}})
    assert pg.main(["--baseline", bench, "--serving", slow, b]) == 1


def test_bundle_version_identity(tmp_path):
    from paddlepaddle_tpu.inference import compile_plan as cp

    bp = _mk_bundle(tmp_path, "v9")
    m = cp.read_manifest(bp)
    assert m["version"] == "v9-vid"
    assert cp.validate_bundle(bp)["version"] == "v9-vid"
    # a pre-version manifest gets a derived identity
    old = os.path.join(str(tmp_path), "old")
    os.makedirs(old)
    with open(os.path.join(old, "manifest.json"), "w") as f:
        json.dump({"format_version": 1, "created_unix": 1234.0,
                   "fingerprint": "a" * 64, "entries": []}, f)
    m = cp.read_manifest(old)
    assert m["version"] == f"{'a' * 12}@1234"
    assert cp.bundle_version_id("b" * 64, 7.9) == f"{'b' * 12}@7"
    # corruption is caught by validate (not by read)
    corrupt = _mk_bundle(tmp_path, "c", corrupt=True)
    with pytest.raises(cp.BundleMismatchError):
        cp.validate_bundle(corrupt)


# -- chaos drill -------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_4x_step_during_rollout_with_preemption(tmp_path):
    """Acceptance drill (real engines): a 4x open-loop traffic step lands
    WHILE a deploy rollout is walking the fleet, and one replica is
    preempted (killed abruptly) mid-rollout. Invariants: every submitted
    future resolves completed-or-typed (zero silently lost), the
    autoscaler reaches its target count, the rollout completes or rolls
    back cleanly (never a mixed-version fleet), and the fleet serves
    afterwards."""
    import paddlepaddle_tpu as paddle
    from paddlepaddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddlepaddle_tpu.resilience.retry import RetryPolicy

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=64, hidden_size=32, layers=2, heads=4, kv_heads=2,
        max_len=128))

    def factory(version):
        return ServingEngine(model, max_batch_size=2, decode_chunk=4,
                             kv_page_size=16)

    pol = FleetPolicy(min_replicas=2, max_replicas=4,
                      scale_up_est_wait_s=0.15, up_streak=2,
                      down_streak=1000, cooldown_up_s=0.3,
                      cooldown_down_s=600.0, interval_s=0.1,
                      health_timeout_s=60.0, drain_timeout_s=15.0)
    fc = FleetController(factory, initial_replicas=2, policy=pol,
                         probe_interval_s=0.1,
                         retry_policy=RetryPolicy(max_attempts=8,
                                                  base_delay=0.02,
                                                  max_delay=0.2))
    fc.start(autoscaler=False)
    rng = np.random.default_rng(3)
    # warm every replica out-of-band so the drill measures scheduling,
    # not first compiles
    for rep in fc.router._replicas:
        rep.client.engine.generate(
            rng.integers(0, 64, (8,)).astype(np.int32), max_new_tokens=2)
    fc.router._probe_once()
    fc.start()                            # autoscaler loop on
    v2 = _mk_bundle(tmp_path, "v2")
    futs, lock, stop = [], threading.Lock(), threading.Event()
    deploy_result = {}

    def traffic():
        t0 = time.monotonic()
        while not stop.is_set():
            gap = 0.20 if time.monotonic() - t0 < 2.0 else 0.05   # 4x step
            p = rng.integers(0, 64, (int(rng.integers(4, 12)),)) \
                .astype(np.int32)
            try:
                f = fc.submit(p, max_new_tokens=3)
            except ServingError:
                time.sleep(gap)
                continue        # typed refusal: visible, not lost
            with lock:
                futs.append(f)
            time.sleep(gap)

    def deployer():
        deploy_result["res"] = fc.deploy(
            v2, canary_requests=2,
            canary_prompt=rng.integers(0, 64, (6,)).astype(np.int32),
            canary_new_tokens=2, canary_timeout=120)

    tthreads = [threading.Thread(target=traffic) for _ in range(2)]
    for t in tthreads:
        t.start()
    time.sleep(1.0)
    dthread = threading.Thread(target=deployer)
    dthread.start()
    time.sleep(1.5)
    # the preemption: one in-rotation replica dies abruptly mid-rollout
    victims = [r for r in fc.router._replicas if r.in_rotation]
    if victims:
        victims[0].client.kill()
    dthread.join(300)
    time.sleep(2.0)                       # let the step pressure register
    stop.set()
    for t in tthreads:
        t.join(30)
    try:
        res = deploy_result.get("res")
        assert res is not None, "deploy never finished"
        with lock:
            taken = list(futs)
        assert len(taken) > 20, "the drill must run under real traffic"
        oks, errs = _resolve_all(taken, timeout=120)
        # zero lost futures: everything resolved, failures are typed/known
        assert len(oks) + len(errs) == len(taken)
        for e in errs:
            assert isinstance(e, (ServingError, RuntimeError,
                                  ConnectionError)), e
        # the fleet absorbed the step: the overwhelming majority completed
        assert len(oks) >= len(taken) * 0.8, \
            f"only {len(oks)}/{len(taken)} completed"
        # the autoscaler reached its target under the step
        assert len(fc.router._replicas) >= 2
        assert fc.target == len(fc.router._replicas)
        # rollout completed or rolled back CLEANLY: never a mixed fleet
        assert res["stage"] in ("done", "canary", "rollout"), res
        live_versions = {fc._versions[r.name]
                         for r in fc.router._replicas}
        if res["ok"]:
            assert fc.rollout["state"] == "done"
            assert live_versions == {v2}
        else:
            assert fc.rollout["state"] == "rolled_back"
            assert live_versions == {None}
        # and the fleet still serves
        fc.router._probe_once()
        out = fc.generate(rng.integers(0, 64, (8,)).astype(np.int32),
                          max_new_tokens=3, timeout=300)
        assert out.shape == (11,)
    finally:
        fc.stop()
