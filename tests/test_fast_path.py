"""Data-plane fast path: snapshot-resident native scheduling parity.

The tentpole contract (ISSUE 6): the native scheduler holds the routable
world resident in C++ — pod arrays, health/circuit avoid marks, adapter
residency, usage-deprioritization marks — re-marshalled once per provider
snapshot version, with the per-pick FFI crossing carrying request scalars
only.  These tests pin:

- **Byte-identical picks** vs the Python ``Scheduler`` oracle under the
  SAME RNG seed, across the health plane (log_only/avoid/strict), an open
  circuit breaker, and the usage advisor — the full PR-3/4/5 seam stack
  over the new snapshot-resident path.
- **pick_many parity**: the batched entry consumes RNG and advisor seams
  pick-for-pick identically to a ``schedule`` loop.
- **Snapshot residency**: the marshal runs once per (version, config,
  avoid-set) — not per pick — and re-runs exactly when one of them moves.
- **Lazy prefix hashes** (satellite): the blake2b chain never runs unless
  a consumer reads ``req.prefix_hashes``; prefix-aware behavior unchanged.
"""

import random

import pytest

from llm_instance_gateway_tpu.gateway import health, resilience
from llm_instance_gateway_tpu.gateway.datastore import Datastore
from llm_instance_gateway_tpu.gateway.metrics_client import (
    FakePodMetricsClient,
)
from llm_instance_gateway_tpu.gateway.provider import Provider, StaticProvider
from llm_instance_gateway_tpu.gateway.scheduling import native
from llm_instance_gateway_tpu.gateway.scheduling.scheduler import (
    Scheduler,
    SchedulingError,
)
from llm_instance_gateway_tpu.gateway.scheduling.types import (
    LazyPrefixHashes,
    LLMRequest,
)
from llm_instance_gateway_tpu.gateway.testing import (
    build_handler_server,
    fake_metrics,
    fake_pod,
    generate_request,
    make_model,
)
from llm_instance_gateway_tpu.gateway.types import Metrics, Pod, PodMetrics

needs_native = pytest.mark.skipif(
    not native.available(),
    reason="native/libligsched.so not buildable on this host",
)


def _pod_metrics(n=6, adapters=("a1", "a2")):
    rng = random.Random(3)
    out = []
    for i in range(n):
        resident = {a: 1 for a in adapters if rng.random() < 0.5}
        out.append(PodMetrics(
            pod=Pod(f"pod-{i}", f"10.0.0.{i}:8000"),
            metrics=Metrics(
                waiting_queue_size=rng.randint(0, 8),
                prefill_queue_size=rng.randint(0, 3),
                kv_cache_usage_percent=round(rng.random() * 0.5, 3),
                kv_tokens_capacity=rng.choice([0, 44_448]),
                kv_tokens_free=rng.randint(1000, 44_448),
                active_adapters=resident,
                max_active_adapters=4,
            ),
        ))
    return out


def versioned_provider(pods: list[PodMetrics]) -> Provider:
    """A REAL Provider (monotonic snapshot version) over static metrics —
    the shape the snapshot-resident cache keys on."""
    ds = Datastore(pods=[pm.pod for pm in pods])
    client = FakePodMetricsClient(
        res={pm.pod.name: pm.metrics for pm in pods})
    provider = Provider(client, ds)
    provider.refresh_pods_once()
    provider.refresh_metrics_once()
    return provider


def _requests(n=64):
    rng = random.Random(5)
    reqs = []
    for i in range(n):
        adapter = rng.choice(["a1", "a2", "missing"])
        reqs.append(LLMRequest(
            model=adapter, resolved_target_model=adapter,
            critical=rng.random() < 0.7,
            prompt_tokens=rng.choice([0, 100, 5000]),
        ))
    return reqs


def _degraded_plane(provider, bad="pod-1", policy="avoid"):
    plane = resilience.ResiliencePlane(
        health.HealthScorer(provider=provider),
        cfg=resilience.ResilienceConfig(health_policy=policy))
    plane.health.update(now=100.0)
    for _ in range(6):
        plane.health.record_upstream(bad, ok=False)
    plane.health.update(now=105.0)
    plane.health.update(now=110.0)
    assert plane.health.state(bad) == health.DEGRADED
    return plane


def _mk_python(provider, seed=7):
    return Scheduler(provider, token_aware=False, prefill_aware=False,
                     prefix_aware=False, rng=random.Random(seed))


def _mk_native(provider, seed=7):
    return native.NativeScheduler(provider, token_aware=False,
                                  prefill_aware=False, prefix_aware=False,
                                  rng=random.Random(seed))


# ---------------------------------------------------------------------------
# Same-RNG parity: snapshot-resident native vs the Python oracle
# ---------------------------------------------------------------------------


@needs_native
class TestSnapshotResidentParity:
    @pytest.mark.parametrize("policy", ["log_only", "avoid"])
    def test_full_plane_same_rng_parity(self, policy):
        """Health plane + open breaker + usage advisor attached to BOTH
        schedulers: the native snapshot path must consume the same RNG
        draws and produce the identical pick sequence."""
        pods = _pod_metrics()
        py_provider = versioned_provider(pods)
        nat_provider = versioned_provider(pods)
        py, nat = _mk_python(py_provider), _mk_native(nat_provider)
        py_plane = _degraded_plane(py_provider, policy=policy)
        nat_plane = _degraded_plane(nat_provider, policy=policy)
        # Open a breaker on a second pod: the avoid set is then the union
        # of an unhealthy pod and a circuit-open pod.
        for plane in (py_plane, nat_plane):
            for _ in range(plane.cfg.trip_consecutive):
                plane.breaker.record("pod-2", ok=False)
            assert plane.breaker.state("pod-2") == resilience.OPEN
        py.health_advisor, nat.health_advisor = py_plane, nat_plane

        class CountingUsage:
            def __init__(self):
                self.picks = []

            def note_pick(self, pod_name, model):
                self.picks.append((pod_name, model))

            def noisy(self):
                return frozenset(["a1"])

        py.usage_advisor, nat.usage_advisor = CountingUsage(), CountingUsage()

        reqs = _requests()
        py_picks = [py.schedule(r).name for r in reqs]
        nat_picks = [nat.schedule(r).name for r in reqs]
        assert py_picks == nat_picks
        # The advisor seams fired identically on both sides.
        assert py.usage_advisor.picks == nat.usage_advisor.picks
        assert py_plane.escape_hatch_total == nat_plane.escape_hatch_total
        if policy == "avoid":
            # An avoided pod serves ONLY when the escape hatch fired (the
            # whole survivor set was avoidable — e.g. affinity narrowed to
            # the degraded holder).
            avoided_picks = sum(1 for p in nat_picks
                                if p in ("pod-1", "pod-2"))
            assert avoided_picks <= nat_plane.escape_hatch_total

    def test_strict_sheds_identically(self):
        pods = _pod_metrics(n=3)
        py_provider = versioned_provider(pods)
        nat_provider = versioned_provider(pods)
        py, nat = _mk_python(py_provider), _mk_native(nat_provider)
        for sched, provider in ((py, py_provider), (nat, nat_provider)):
            plane = resilience.ResiliencePlane(
                health.HealthScorer(provider=provider),
                cfg=resilience.ResilienceConfig(health_policy="strict"))
            plane.health.update(now=100.0)
            for pm in pods:
                for _ in range(plane.cfg.trip_consecutive):
                    plane.breaker.record(pm.pod.name, ok=False)
            sched.health_advisor = plane
        req = LLMRequest(model="a1", resolved_target_model="a1",
                         critical=True)
        with pytest.raises(SchedulingError) as py_err:
            py.schedule(req)
        with pytest.raises(SchedulingError) as nat_err:
            nat.schedule(req)
        assert py_err.value.shed and nat_err.value.shed

    def test_escape_hatch_full_pool_parity(self):
        """Every pod avoidable under avoid: both sides serve the full set
        (escape hatch) and count it."""
        pods = _pod_metrics(n=4)
        py_provider = versioned_provider(pods)
        nat_provider = versioned_provider(pods)
        py, nat = _mk_python(py_provider), _mk_native(nat_provider)
        for sched, provider in ((py, py_provider), (nat, nat_provider)):
            plane = resilience.ResiliencePlane(
                health.HealthScorer(provider=provider),
                cfg=resilience.ResilienceConfig(health_policy="avoid"))
            plane.health.update(now=100.0)
            for pm in pods:
                for _ in range(plane.cfg.trip_consecutive):
                    plane.breaker.record(pm.pod.name, ok=False)
            sched.health_advisor = plane
        reqs = _requests(32)
        assert [py.schedule(r).name for r in reqs] == \
            [nat.schedule(r).name for r in reqs]
        assert py.health_advisor.escape_hatch_total == \
            nat.health_advisor.escape_hatch_total > 0


# ---------------------------------------------------------------------------
# pick_many: the batched FFI entry
# ---------------------------------------------------------------------------


@needs_native
class TestPickMany:
    def test_matches_schedule_loop(self):
        pods = _pod_metrics()
        loop_sched = _mk_native(versioned_provider(pods), seed=13)
        batch_sched = _mk_native(versioned_provider(pods), seed=13)
        reqs = _requests(48)
        loop_picks = [loop_sched.schedule(r).name for r in reqs]
        batch_picks = [p.name for p in batch_sched.pick_many(reqs)]
        assert loop_picks == batch_picks

    def test_matches_python_oracle(self):
        pods = _pod_metrics()
        py = _mk_python(versioned_provider(pods), seed=21)
        nat = _mk_native(versioned_provider(pods), seed=21)
        reqs = _requests(48)
        assert [py.schedule(r).name for r in reqs] == \
            [p.name for p in nat.pick_many(reqs)]

    def test_empty_batch(self):
        nat = _mk_native(versioned_provider(_pod_metrics()))
        assert nat.pick_many([]) == []

    def test_sheds_on_saturated_pool(self):
        pods = [PodMetrics(
            pod=Pod("p0", "10.0.0.1:8000"),
            metrics=Metrics(waiting_queue_size=500,
                            kv_cache_usage_percent=0.99))]
        nat = _mk_native(versioned_provider(pods))
        sheddable = LLMRequest(model="m", resolved_target_model="m",
                               critical=False)
        with pytest.raises(SchedulingError) as err:
            nat.pick_many([sheddable])
        assert err.value.shed


# ---------------------------------------------------------------------------
# Snapshot residency: marshal cadence, not pick cadence
# ---------------------------------------------------------------------------


@needs_native
class TestSnapshotResidency:
    def _counting(self, sched):
        calls = []
        orig = sched._marshal

        def counted(state, pods, policy, bad, fairness, noisy,
                    placement="log_only", rmap=None):
            calls.append(len(pods))
            return orig(state, pods, policy, bad, fairness, noisy,
                        placement, rmap)

        sched._marshal = counted
        return calls

    def test_marshal_once_per_version(self):
        pods = _pod_metrics()
        provider = versioned_provider(pods)
        sched = _mk_native(provider)
        calls = self._counting(sched)
        reqs = _requests(32)
        for r in reqs:
            sched.schedule(r)
        assert len(calls) == 1  # 32 picks, ONE tick-time marshal

    def test_remarshals_on_version_bump(self):
        pods = _pod_metrics()
        provider = versioned_provider(pods)
        sched = _mk_native(provider)
        calls = self._counting(sched)
        req = _requests(1)[0]
        sched.schedule(req)
        provider.update_pod_metrics(pods[0].pod, pods[0].metrics)
        sched.schedule(req)
        assert len(calls) == 2

    def test_remarshals_on_config_update(self):
        provider = versioned_provider(_pod_metrics())
        sched = _mk_native(provider)
        calls = self._counting(sched)
        req = _requests(1)[0]
        sched.schedule(req)
        sched.update_config(sched.cfg)
        sched.schedule(req)
        assert len(calls) == 2

    def test_remarshals_on_avoid_set_change(self):
        pods = _pod_metrics()
        provider = versioned_provider(pods)
        sched = _mk_native(provider)
        plane = _degraded_plane(provider, policy="avoid")
        sched.health_advisor = plane
        calls = self._counting(sched)
        req = _requests(1)[0]
        sched.schedule(req)
        sched.schedule(req)
        assert len(calls) == 1  # same avoid set: resident state reused
        for _ in range(plane.cfg.trip_consecutive):
            plane.breaker.record("pod-3", ok=False)
        sched.schedule(req)
        assert len(calls) == 2  # breaker opened -> avoid set moved

    def test_versionless_provider_marshals_per_pick(self):
        """StaticProvider has no snapshot(): semantics identical, the
        amortization is lost (documented fallback rule)."""
        pods = _pod_metrics()
        sched = _mk_native(StaticProvider(pods))
        calls = self._counting(sched)
        reqs = _requests(4)
        for r in reqs:
            sched.schedule(r)
        assert len(calls) == 4
        # ... and picks still match the Python oracle.
        py = _mk_python(StaticProvider(pods))
        nat = _mk_native(StaticProvider(pods))
        assert [py.schedule(r).name for r in reqs] == \
            [nat.schedule(r).name for r in reqs]


# ---------------------------------------------------------------------------
# Lazy prefix hashes (satellite: ADVICE item 5)
# ---------------------------------------------------------------------------


class TestLazyPrefixHashes:
    def test_thunk_never_runs_unless_read(self):
        ran = []
        lazy = LazyPrefixHashes(lambda: ran.append(1) or (b"h1", b"h2"))
        assert not ran  # construction is free
        assert len(lazy) == 2
        assert ran == [1]
        assert bool(lazy)
        assert list(lazy) == [b"h1", b"h2"]
        assert lazy[0] == b"h1"
        assert ran == [1]  # resolved ONCE, then cached

    def test_matches_eager_tuple_semantics(self):
        eager = (b"x", b"y")
        lazy = LazyPrefixHashes(lambda: eager)
        assert lazy == eager
        assert lazy == [b"x", b"y"]
        assert hash(lazy) == hash(eager)
        assert bool(LazyPrefixHashes(tuple)) is False

    def test_prefix_unaware_server_never_hashes(self, monkeypatch):
        """The satellite regression: a prefix-unaware build must not run
        the blake2b chain at all."""
        from llm_instance_gateway_tpu.gateway.handlers import (
            request as request_handlers,
        )
        from llm_instance_gateway_tpu.gateway.handlers.messages import (
            RequestBody,
        )
        from llm_instance_gateway_tpu.gateway.handlers.server import (
            RequestContext,
        )

        calls = []
        orig = request_handlers.prefix_hashes

        def counted(text, model=""):
            calls.append(model)
            return orig(text, model=model)

        monkeypatch.setattr(request_handlers, "prefix_hashes", counted)
        pods = {fake_pod(0): fake_metrics(adapters={"m": 1})}
        unaware = build_handler_server(pods, [make_model("m")],
                                       prefix_aware=False)
        res = unaware.process(RequestContext(),
                              RequestBody(body=generate_request("m")))
        assert res.set_headers  # scheduled fine
        assert calls == []  # the chain never ran

        aware = build_handler_server(pods, [make_model("m")])
        res = aware.process(RequestContext(),
                            RequestBody(body=generate_request("m")))
        assert res.set_headers
        assert calls == ["m"]  # prefix-aware behavior unchanged: one chain

    def test_prefix_aware_stickiness_through_lazy(self):
        """Prefix-aware routing still works through the lazy facade: two
        requests sharing a long prefix land on the same replica."""
        from llm_instance_gateway_tpu.gateway.handlers.messages import (
            RequestBody,
        )
        from llm_instance_gateway_tpu.gateway.handlers.server import (
            DEFAULT_TARGET_POD_HEADER,
            RequestContext,
        )
        from llm_instance_gateway_tpu.gateway.scheduling.prefix_affinity import (
            PREFIX_BLOCK_CHARS,
        )

        pods = {fake_pod(i): fake_metrics() for i in range(8)}
        server = build_handler_server(pods, [make_model("m")])
        prompt = "s" * (PREFIX_BLOCK_CHARS * 4)
        picks = set()
        for k in range(6):
            res = server.process(
                RequestContext(),
                RequestBody(body=generate_request("m", prompt=prompt)))
            picks.add(res.set_headers[DEFAULT_TARGET_POD_HEADER])
        assert len(picks) == 1  # sticky: every repeat on the holder
