"""tpuchaos + resilience-layer tests.

Three tiers, mirroring the subsystem:

* unit — the schedule DSL, the seeded injector, RetryPolicy/RetryBudget/
  CircuitBreaker semantics;
* integration — the four clients and the fleet router under injected
  faults (mid-response FIN replay safety, connect-phase failover,
  hedging, breaker exclusion, admin-state replay, stream resume);
* acceptance — the full crash drill: 2 replica SUBPROCESSES under
  sustained idempotent load, SIGKILL one mid-stream, assert eject /
  zero-visible-failure failover / stream resume / rejoin-with-replay,
  writing the ``CHAOS_r01.json`` record (seed-deterministic fault
  counts) under ``tmp_path``.

Everything here must stay green under ``TPUSAN=1`` (all
chaos/resilience locks are sanitizer-adopted named locks).
"""

import json
import random
import threading
import time

import grpc
import numpy as np
import pytest
import requests

from tritonclient_tpu import chaos
from tritonclient_tpu.chaos import PlanError, Rule
from tritonclient_tpu.chaos._controller import ChaosController
from tritonclient_tpu.fleet import FleetRouter, FleetServer, ReplicaSet
from tritonclient_tpu.fleet._policy import affinity_select
from tritonclient_tpu.fleet._replica import ReplicaState, http_call
from tritonclient_tpu.fleet.serve import FleetDeviceModel
from tritonclient_tpu.protocol import GRPCInferenceServiceStub, pb
from tritonclient_tpu.protocol._literals import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    HEADER_IDEMPOTENCY_KEY,
    HEDGE_OUTCOME_HEDGE,
    RETRY_REASON_CONNECT,
    RETRY_REASON_IDEMPOTENT,
    RETRY_REASON_SEND,
    RETRY_REASON_STATUS,
    shm_admin_path,
)
from tritonclient_tpu.resilience import (
    BreakerOpenError,
    CircuitBreaker,
    RetryBudget,
    RetryPolicy,
)
from tritonclient_tpu.server import InferenceServer
from tritonclient_tpu.utils import InferenceServerException

import sys

sys.path.insert(0, "scripts")
from check_metrics_exposition import check_exposition  # noqa: E402

SERVICE_MS = 5


def _infer_body(value=0, shm_region=None, byte_size=64):
    inp = {
        "name": "INPUT", "datatype": "INT32", "shape": [1, 16],
    }
    if shm_region is not None:
        inp["parameters"] = {
            "shared_memory_region": shm_region,
            "shared_memory_byte_size": byte_size,
            "shared_memory_offset": 0,
        }
    else:
        inp["data"] = [value + i for i in range(16)]
    return {"inputs": [inp]}


def _eventually(predicate, timeout_s=5.0, poll_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(poll_s)  # tpulint: disable=TPU001 (sync test poll)
    return predicate()


def _grpc_request(model="fleet_device"):
    req = pb.ModelInferRequest(model_name=model)
    t = req.inputs.add()
    t.name, t.datatype = "INPUT", "INT32"
    t.shape.extend([1, 16])
    req.raw_input_contents.append(np.arange(16, dtype=np.int32).tobytes())
    return req


def _count(replica, model="fleet_device"):
    return replica.core._stats[model].inference_count


# --------------------------------------------------------------------------- #
# unit: schedule DSL                                                          #
# --------------------------------------------------------------------------- #


class TestPlanDSL:
    def test_parse_rules(self):
        plan = chaos.Plan(
            "http.response=reset@nth=3; fleet.exchange.connect=refused"
            "@p=0.25@max=2; grpc.call=latency@ms=40@after=1@until=2.5",
            seed=3,
        )
        specs = [r.spec() for r in plan.rules]
        assert specs[0] == "http.response=reset@nth=3"
        assert "p=0.25" in specs[1] and "max=2" in specs[1]
        assert "ms=40" in specs[2] and "after=1" in specs[2]

    def test_unknown_fault_and_key_rejected(self):
        with pytest.raises(PlanError):
            chaos.Plan("a=explode")
        with pytest.raises(PlanError):
            chaos.Plan("a=reset@frequency=2")
        with pytest.raises(PlanError):
            chaos.Plan("just-a-site")

    def test_nth_every_max_triggers(self):
        nth = Rule("s", "reset", nth=3)
        nth.seed(0)
        assert [nth.decide(0.0) for _ in range(5)] == [
            False, False, True, False, False,
        ]
        every = Rule("s", "reset", every=2, max_count=2)
        every.seed(0)
        assert [every.decide(0.0) for _ in range(6)] == [
            False, True, False, True, False, False,  # max=2 exhausted
        ]

    def test_time_window(self):
        rule = Rule("s", "latency", ms=1, after_s=1.0, until_s=2.0)
        rule.seed(0)
        assert not rule.decide(0.5)
        assert rule.decide(1.5)
        assert not rule.decide(2.5)

    def test_probability_deterministic_per_seed(self):
        def draws(seed):
            rule = Rule("s", "reset", p=0.5)
            rule.seed(seed)
            return [rule.decide(0.0) for _ in range(32)]

        assert draws(7) == draws(7)
        assert draws(7) != draws(8)
        assert 4 < sum(draws(7)) < 28  # actually probabilistic

    def test_wildcard_site(self):
        rule = Rule("*", "reset")
        assert rule.matches("anything.at.all")


# --------------------------------------------------------------------------- #
# unit: the injector                                                          #
# --------------------------------------------------------------------------- #


class TestInjector:
    def test_off_is_noop(self):
        chaos.disable()  # the CI chaos lane env-activates an empty plan
        assert not chaos.active()
        chaos.fire("http.connect")  # nothing raised, nothing recorded
        assert chaos.injections() == []

    def test_fault_exceptions_and_records(self):
        cases = [
            ("refused", ConnectionRefusedError),
            ("reset", ConnectionResetError),
            ("partial", BrokenPipeError),
            ("enomem", OSError),
        ]
        for fault, exc_type in cases:
            with chaos.session(1, f"s={fault}@nth=1"):
                with pytest.raises(exc_type) as excinfo:
                    chaos.fire("s")
                assert isinstance(excinfo.value, chaos.ChaosInjection)
                assert chaos.summary()["injected"] == 1

    def test_latency_fault_sleeps_not_raises(self):
        with chaos.session(1, "s=latency@ms=30@nth=1"):
            t0 = time.monotonic()
            chaos.fire("s")
            assert time.monotonic() - t0 >= 0.02
            assert chaos.summary()["injected"] == 1

    def test_grpc_unavailable_duck_type(self):
        with chaos.session(1, "s=unavailable@nth=1"):
            with pytest.raises(grpc.RpcError) as excinfo:
                chaos.fire("s")
            assert excinfo.value.code() == grpc.StatusCode.UNAVAILABLE

    def test_survival_accounting(self):
        """An operation that retries through its injected fault marks it
        survived; one that gives up does not."""
        with chaos.session(1, "s=reset@nth=1"):
            with chaos.operation("op"):
                for _ in range(2):  # first call injected, second clean
                    try:
                        chaos.fire("s")
                        break
                    except ConnectionResetError:
                        continue
            summary = chaos.summary()
            assert summary == {
                "tool": "tpuchaos", "seed": 1, "plan": "s=reset@nth=1",
                "injected": 1, "survived": 1,
                "by_site": {"s": {"injected": 1, "survived": 1}},
            }

    def test_unsurvived_when_operation_raises(self):
        with chaos.session(1, "s=reset@nth=1"):
            with pytest.raises(ConnectionResetError):
                with chaos.operation("op"):
                    chaos.fire("s")
            assert chaos.summary()["survived"] == 0

    def test_report_json_and_sarif(self, tmp_path):
        with chaos.session(9, "s=reset@nth=1"):
            with pytest.raises(ConnectionResetError):
                chaos.fire("s")
            jpath = tmp_path / "chaos.json"
            chaos.write_report(str(jpath))
            doc = json.loads(jpath.read_text())
            assert doc["seed"] == 9 and doc["injected"] == 1
            assert doc["faults"][0]["site"] == "s"
            spath = tmp_path / "chaos.sarif"
            chaos.write_report(str(spath))
            sarif = json.loads(spath.read_text())
            run = sarif["runs"][0]
            assert run["tool"]["driver"]["name"] == "tpuchaos"
            assert len(run["results"]) == 1

    def test_env_seed_parse(self, monkeypatch):
        monkeypatch.setenv("TPUCHAOS", "1337:")
        assert chaos.env_seed() == 1337
        monkeypatch.delenv("TPUCHAOS")
        assert chaos.env_seed(5) == 5


# --------------------------------------------------------------------------- #
# unit: RetryPolicy / RetryBudget                                             #
# --------------------------------------------------------------------------- #


class TestRetryPolicy:
    def test_classify_matrix(self):
        policy = RetryPolicy()
        assert policy.classify("connect") == RETRY_REASON_CONNECT
        assert policy.classify("send") == RETRY_REASON_SEND
        assert policy.classify("response") is None  # may have executed
        assert (
            policy.classify("response", idempotent=True)
            == RETRY_REASON_IDEMPOTENT
        )
        assert policy.classify("response", status=503) == RETRY_REASON_STATUS
        assert policy.classify("response", status=429) == RETRY_REASON_STATUS
        assert policy.classify("response", status=500) is None

    def test_full_jitter_bounds_and_determinism(self):
        a = RetryPolicy(base_delay_s=0.1, max_delay_s=1.0,
                        rng=random.Random(42))
        b = RetryPolicy(base_delay_s=0.1, max_delay_s=1.0,
                        rng=random.Random(42))
        delays_a = [a.backoff_s(i) for i in range(6)]
        delays_b = [b.backoff_s(i) for i in range(6)]
        assert delays_a == delays_b  # seeded → deterministic
        for i, d in enumerate(delays_a):
            assert 0.0 <= d <= min(1.0, 0.1 * (2.0 ** i))

    def test_retry_after_overrides_and_caps(self):
        policy = RetryPolicy(max_delay_s=0.5)
        assert policy.backoff_s(0, retry_after_s=0.2) == 0.2
        assert policy.backoff_s(0, retry_after_s=9.0) == 0.5  # capped

    def test_attempt_cap_and_counters(self):
        policy = RetryPolicy(max_attempts=3)
        assert policy.should_retry(0, RETRY_REASON_CONNECT)
        assert policy.should_retry(1, RETRY_REASON_CONNECT)
        assert not policy.should_retry(2, RETRY_REASON_CONNECT)
        assert not policy.should_retry(0, None)
        snap = policy.snapshot()
        assert snap[RETRY_REASON_CONNECT] == 2 and snap["total"] == 2

    def test_budget_exhaustion_surfaces_original_error(self):
        policy = RetryPolicy(max_attempts=5,
                             budget=RetryBudget(capacity=2, refill_ratio=0))
        allowed = [
            policy.should_retry(0, RETRY_REASON_CONNECT) for _ in range(4)
        ]
        assert allowed == [True, True, False, False]
        assert policy.snapshot()["exhausted"] == 2

    def test_budget_refills_on_success(self):
        budget = RetryBudget(capacity=1, refill_ratio=0.5)
        assert budget.try_spend()
        assert not budget.try_spend()
        budget.note_success()
        budget.note_success()
        assert budget.try_spend()


# --------------------------------------------------------------------------- #
# unit: CircuitBreaker                                                        #
# --------------------------------------------------------------------------- #


class TestCircuitBreaker:
    def test_open_half_open_close_cycle(self):
        clock = [0.0]
        breaker = CircuitBreaker("ep", failure_threshold=2,
                                 reset_timeout_s=1.0,
                                 clock=lambda: clock[0])
        assert breaker.state == BREAKER_CLOSED and breaker.allow()
        breaker.on_failure()
        assert breaker.state == BREAKER_CLOSED  # under threshold
        breaker.on_failure()
        assert breaker.state == BREAKER_OPEN
        assert breaker.blocked()
        assert not breaker.allow()  # fast failure, no I/O
        clock[0] = 1.5
        assert not breaker.blocked()  # cooldown elapsed
        assert breaker.state == BREAKER_HALF_OPEN
        assert breaker.allow()       # the single probe
        assert not breaker.allow()   # second caller blocked mid-probe
        breaker.on_success()
        assert breaker.state == BREAKER_CLOSED
        assert breaker.snapshot()["opens"] == 1

    def test_half_open_probe_failure_reopens(self):
        clock = [0.0]
        breaker = CircuitBreaker("ep", failure_threshold=1,
                                 reset_timeout_s=1.0,
                                 clock=lambda: clock[0])
        breaker.on_failure()
        clock[0] = 1.1
        assert breaker.allow()
        breaker.on_failure()  # probe failed
        assert breaker.state == BREAKER_OPEN
        assert not breaker.allow()
        assert breaker.snapshot()["opens"] == 2

    def test_check_raises_and_state_values(self):
        breaker = CircuitBreaker("ep", failure_threshold=1,
                                 reset_timeout_s=60.0)
        assert breaker.state_value() == 0
        breaker.on_failure()
        assert breaker.state_value() == 2
        with pytest.raises(BreakerOpenError) as excinfo:
            breaker.check()
        assert "ep" in str(excinfo.value)


# --------------------------------------------------------------------------- #
# integration: HTTP client under injection                                    #
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def server():
    srv = InferenceServer(
        models=[FleetDeviceModel(service_ms=SERVICE_MS)]
    ).start()
    yield srv
    srv.stop()


def _http_client(server, **kwargs):
    from tritonclient_tpu.http import InferenceServerClient, InferInput

    client = InferenceServerClient(server.http_address, **kwargs)
    inputs = [InferInput("INPUT", [1, 16], "INT32")]
    inputs[0].set_data_from_numpy(
        np.arange(16, dtype=np.int32).reshape(1, 16)
    )
    return client, inputs


class TestHTTPClientResilience:
    def test_connect_fault_survived_by_retry(self, server):
        policy = RetryPolicy(max_attempts=3, base_delay_s=0.01,
                             rng=random.Random(0))
        client, inputs = _http_client(server, retry_policy=policy)
        try:
            with chaos.session(1, "http.connect=refused@nth=1"):
                result = client.infer("fleet_device", inputs)
                assert result.as_numpy("OUTPUT") is not None
                summary = chaos.summary()
            assert summary["injected"] == 1
            assert summary["survived"] == 1
            assert policy.snapshot()[RETRY_REASON_CONNECT] == 1
        finally:
            client.close()

    def test_mid_response_fin_not_replayed_without_key(self, server):
        """Post-send failure + no idempotency key: the policy must NOT
        replay (the server may have executed the request)."""
        policy = RetryPolicy(max_attempts=3, base_delay_s=0.01)
        client, inputs = _http_client(server, retry_policy=policy)
        try:
            before = _count(server)
            with chaos.session(1, "http.response=reset@nth=1"):
                with pytest.raises(InferenceServerException):
                    client.infer("fleet_device", inputs)
            assert policy.snapshot()["total"] == 0
            # The request DID execute exactly once server-side: the FIN
            # hit the response read, not the request.
            assert _eventually(lambda: _count(server) == before + 1)
        finally:
            client.close()

    def test_mid_response_fin_replayed_with_key(self, server):
        policy = RetryPolicy(max_attempts=3, base_delay_s=0.01,
                             rng=random.Random(0))
        client, inputs = _http_client(server, retry_policy=policy)
        try:
            before = _count(server)
            with chaos.session(1, "http.response=reset@nth=1"):
                result = client.infer("fleet_device", inputs,
                                      idempotency_key="req-1")
            assert result.as_numpy("OUTPUT") is not None
            assert policy.snapshot()[RETRY_REASON_IDEMPOTENT] == 1
            # Double execution is the documented cost of the key.
            assert _eventually(lambda: _count(server) == before + 2)
        finally:
            client.close()

    def test_budget_exhaustion_returns_original_error(self, server):
        policy = RetryPolicy(
            max_attempts=5, base_delay_s=0.01,
            budget=RetryBudget(capacity=1, refill_ratio=0),
        )
        client, inputs = _http_client(server, retry_policy=policy)
        try:
            with chaos.session(1, "http.connect=refused"):  # every call
                with pytest.raises(InferenceServerException) as excinfo:
                    client.infer("fleet_device", inputs)
            assert "refused" in str(excinfo.value)
            snap = policy.snapshot()
            assert snap[RETRY_REASON_CONNECT] == 1  # budget allowed one
            assert snap["exhausted"] >= 1
        finally:
            client.close()

    def test_client_breaker_fails_fast(self, server):
        breaker = CircuitBreaker(server.http_address,
                                 failure_threshold=2, reset_timeout_s=60.0)
        client, inputs = _http_client(server, circuit_breaker=breaker)
        try:
            with chaos.session(1, "http.connect=refused"):
                for _ in range(2):
                    with pytest.raises(InferenceServerException):
                        client.infer("fleet_device", inputs)
            # Chaos off again: the OPEN breaker still fails fast, no I/O.
            with pytest.raises(BreakerOpenError):
                client.infer("fleet_device", inputs)
        finally:
            client.close()


# --------------------------------------------------------------------------- #
# integration: aio clients under injection                                    #
# --------------------------------------------------------------------------- #


class TestAioClientResilience:
    def test_aio_http_status_retry_and_connect_refused(self, server):
        import asyncio

        from tritonclient_tpu.http.aio import (
            InferenceServerClient as AioClient,
        )
        from tritonclient_tpu.http import InferInput

        async def scenario():
            policy = RetryPolicy(max_attempts=3, base_delay_s=0.01,
                                 rng=random.Random(0))
            client = AioClient(server.http_address, retry_policy=policy)
            inputs = [InferInput("INPUT", [1, 16], "INT32")]
            inputs[0].set_data_from_numpy(
                np.arange(16, dtype=np.int32).reshape(1, 16)
            )
            try:
                result = await client.infer("fleet_device", inputs)
                assert result.as_numpy("OUTPUT") is not None
                return policy.snapshot()
            finally:
                await client.close()

        snapshot = asyncio.run(scenario())
        assert snapshot["total"] == 0  # clean path, no spurious retries

    def test_aio_grpc_retry_on_unavailable(self):
        import asyncio

        from tritonclient_tpu.grpc.aio import (
            InferenceServerClient as AioGrpcClient,
        )
        from tritonclient_tpu.grpc import InferInput

        srv = InferenceServer(
            models=[FleetDeviceModel(service_ms=SERVICE_MS)], http=False
        ).start()

        async def scenario():
            policy = RetryPolicy(max_attempts=3, base_delay_s=0.01,
                                 rng=random.Random(0))
            client = AioGrpcClient(srv.grpc_address, retry_policy=policy)
            inputs = [InferInput("INPUT", [1, 16], "INT32")]
            inputs[0].set_data_from_numpy(
                np.arange(16, dtype=np.int32).reshape(1, 16)
            )
            try:
                result = await client.infer("fleet_device", inputs)
                assert result.as_numpy("OUTPUT") is not None
                return policy.snapshot()
            finally:
                await client.close()

        try:
            snapshot = asyncio.run(scenario())
            assert snapshot["total"] == 0
        finally:
            srv.stop()


# --------------------------------------------------------------------------- #
# integration: gRPC client — injected UNAVAILABLE + reconnect bound           #
# --------------------------------------------------------------------------- #


class TestGrpcClientResilience:
    def test_injected_unavailable_retried(self, server):
        from tritonclient_tpu.grpc import InferenceServerClient, InferInput

        policy = RetryPolicy(max_attempts=3, base_delay_s=0.01,
                             rng=random.Random(0))
        client = InferenceServerClient(server.grpc_address,
                                       retry_policy=policy)
        inputs = [InferInput("INPUT", [1, 16], "INT32")]
        inputs[0].set_data_from_numpy(
            np.arange(16, dtype=np.int32).reshape(1, 16)
        )
        try:
            with chaos.session(1, "grpc.call=unavailable@nth=1"):
                result = client.infer("fleet_device", inputs)
                assert result.as_numpy("OUTPUT") is not None
                assert chaos.summary()["survived"] == 1
            assert policy.snapshot()[RETRY_REASON_CONNECT] == 1
        finally:
            client.close()

    def test_reconnect_backoff_bound(self):
        """A dropped channel must reconnect within the configured bound
        (sane-default channel args), not gRPC's multi-ten-second default
        backoff schedule."""
        import socket as socket_module

        from tritonclient_tpu.grpc import InferenceServerClient, InferInput

        with socket_module.socket() as probe:
            probe.bind(("127.0.0.1", 0))
            port = probe.getsockname()[1]
        srv = InferenceServer(
            models=[FleetDeviceModel(service_ms=SERVICE_MS)],
            http=False, grpc_port=port,
        ).start()
        client = InferenceServerClient(
            f"127.0.0.1:{port}",
            initial_reconnect_backoff_ms=100,
            max_reconnect_backoff_ms=500,
        )
        inputs = [InferInput("INPUT", [1, 16], "INT32")]
        inputs[0].set_data_from_numpy(
            np.arange(16, dtype=np.int32).reshape(1, 16)
        )
        try:
            assert client.infer("fleet_device", inputs) is not None
            srv.stop()
            with pytest.raises(InferenceServerException):
                client.infer("fleet_device", inputs, client_timeout=2)
            # Channel is now in its backoff schedule. Bring the server
            # back on the SAME port and require recovery well under the
            # ~20 s a default-config channel can stay dark.
            srv = InferenceServer(
                models=[FleetDeviceModel(service_ms=SERVICE_MS)],
                http=False, grpc_port=port,
            ).start()
            t0 = time.monotonic()
            deadline = t0 + 8.0
            recovered = False
            while time.monotonic() < deadline:
                try:
                    client.infer("fleet_device", inputs, client_timeout=2)
                    recovered = True
                    break
                except InferenceServerException:
                    time.sleep(0.05)  # tpulint: disable=TPU001
            elapsed = time.monotonic() - t0
            assert recovered, "channel never reconnected"
            assert elapsed < 8.0
        finally:
            client.close()
            srv.stop()


# --------------------------------------------------------------------------- #
# integration: fleet failover / hedging / breaker                             #
# --------------------------------------------------------------------------- #


def _fleet(n=2, service_ms=SERVICE_MS, **router_kwargs):
    replicas = [
        InferenceServer(
            models=[FleetDeviceModel(service_ms=service_ms)]
        ).start()
        for _ in range(n)
    ]
    replica_set = ReplicaSet(probe_interval_s=10)  # manual probes only
    router = FleetRouter(replicas=replica_set, **router_kwargs)
    for i, r in enumerate(replicas):
        router.add_replica(f"r{i}", r.http_address, r.grpc_address)
    replica_set.probe_once()
    server = FleetServer(router)
    server.start()
    return replicas, replica_set, router, server


def _teardown_fleet(replicas, server):
    server.stop()
    for r in replicas:
        try:
            r.stop()
        except Exception:
            pass


class TestFleetFailover:
    def test_mid_response_fin_not_replayed_without_key(self):
        """The satellite-1 regression: a mid-response FIN after the
        replica executed must NOT be replayed for a key-less infer —
        the client sees 502 and the fleet executed exactly once."""
        replicas, _, router, server = _fleet()
        try:
            base = f"http://{server.http_address}"
            with chaos.session(1, "fleet.exchange.response=reset@nth=1"):
                resp = requests.post(
                    base + "/v2/models/fleet_device/infer",
                    json=_infer_body(),
                )
            assert resp.status_code == 502
            assert "response phase" in resp.json()["error"]
            assert router.retry_policy.snapshot()["total"] == 0
            # At MOST one execution (0 when the router's closed proxy
            # connection let the replica's disconnect watcher shed the
            # work first) — the double-execution bug would make this 2.
            time.sleep(0.1)  # tpulint: disable=TPU001 (let executions land)
            total = _count(replicas[0]) + _count(replicas[1])
            assert total <= 1
        finally:
            _teardown_fleet(replicas, server)

    def test_mid_response_fin_replayed_with_key(self):
        replicas, _, router, server = _fleet()
        try:
            base = f"http://{server.http_address}"
            with chaos.session(1, "fleet.exchange.response=reset@nth=1"):
                resp = requests.post(
                    base + "/v2/models/fleet_device/infer",
                    json=_infer_body(),
                    headers={HEADER_IDEMPOTENCY_KEY: "k1"},
                )
            assert resp.status_code == 200
            snap = router.retry_policy.snapshot()
            assert snap[RETRY_REASON_IDEMPOTENT] == 1
            # The replay was authorized; the caller accepted up to
            # double execution (the first attempt may also have been
            # shed by the replica's disconnect watcher).
            total = _count(replicas[0]) + _count(replicas[1])
            assert 1 <= total <= 2
        finally:
            _teardown_fleet(replicas, server)

    def test_connect_phase_failover_is_invisible(self):
        """Connect-phase failures are provably pre-execution: failover
        happens even without an idempotency key and the client sees a
        clean 200."""
        replicas, _, router, server = _fleet()
        try:
            base = f"http://{server.http_address}"
            with chaos.session(1, "fleet.exchange.connect=refused@nth=1"):
                resp = requests.post(
                    base + "/v2/models/fleet_device/infer",
                    json=_infer_body(),
                )
            assert resp.status_code == 200
            snap = router.retry_policy.snapshot()
            assert snap[RETRY_REASON_CONNECT] == 1
            metrics = requests.get(base + "/metrics").text
            assert 'nv_client_retries_total{reason="connect"} 1' in metrics
            assert check_exposition(metrics) == []
        finally:
            _teardown_fleet(replicas, server)

    def test_dead_replica_failover_and_breaker_opens(self):
        """A crashed replica (still READY in stale membership): keyed
        requests fail over with zero client-visible failures, the
        breaker opens after the threshold, and later requests skip the
        corpse without new retries."""
        replicas, replica_set, router, server = _fleet(
            breaker_failure_threshold=3, breaker_reset_s=60.0,
        )
        try:
            base = f"http://{server.http_address}"
            replicas[0].stop()  # crash; membership still says READY
            assert replica_set.get("r0").state == ReplicaState.READY
            for i in range(6):
                resp = requests.post(
                    base + "/v2/models/fleet_device/infer",
                    json=_infer_body(i),
                    headers={HEADER_IDEMPOTENCY_KEY: f"k{i}"},
                )
                assert resp.status_code == 200
            assert router.breaker_for("r0").state == BREAKER_OPEN
            retries_at_open = router.retry_policy.snapshot()["total"]
            assert retries_at_open >= 1
            for i in range(5):
                resp = requests.post(
                    base + "/v2/models/fleet_device/infer",
                    json=_infer_body(i),
                    headers={HEADER_IDEMPOTENCY_KEY: f"post{i}"},
                )
                assert resp.status_code == 200
            # Breaker exclusion means no further failover retries burn.
            assert router.retry_policy.snapshot()["total"] == retries_at_open
            metrics = requests.get(base + "/metrics").text
            assert 'nv_client_breaker_state{endpoint="r0"} 2' in metrics
            assert check_exposition(metrics) == []
        finally:
            _teardown_fleet(replicas, server)

    def test_grpc_unary_failover(self):
        replicas, replica_set, router, server = _fleet()
        try:
            replicas[0].stop()
            channel = grpc.insecure_channel(server.grpc_address)
            stub = GRPCInferenceServiceStub(channel)
            try:
                for i in range(4):
                    reply = stub.ModelInfer(
                        _grpc_request(),
                        metadata=((HEADER_IDEMPOTENCY_KEY, f"g{i}"),),
                    )
                    assert reply.model_name == "fleet_device"
            finally:
                channel.close()
        finally:
            _teardown_fleet(replicas, server)


class TestHedging:
    def test_hedge_wins_on_slow_primary(self):
        """Primary replica is slow (300 ms device time); the hedge fires
        at 40 ms onto the fast replica and wins."""
        slow = InferenceServer(
            models=[FleetDeviceModel(service_ms=300)]
        ).start()
        fast = InferenceServer(
            models=[FleetDeviceModel(service_ms=5)]
        ).start()
        replica_set = ReplicaSet(probe_interval_s=10)
        router = FleetRouter(replicas=replica_set, hedge_us=40_000)
        # Name order makes the slow replica the least-outstanding pick.
        router.add_replica("r0", slow.http_address, slow.grpc_address)
        router.add_replica("r1", fast.http_address, fast.grpc_address)
        replica_set.probe_once()
        server = FleetServer(router, grpc=False)
        server.start()
        try:
            base = f"http://{server.http_address}"
            t0 = time.monotonic()
            resp = requests.post(
                base + "/v2/models/fleet_device/infer",
                json=_infer_body(),
                headers={HEADER_IDEMPOTENCY_KEY: "h1"},
            )
            elapsed = time.monotonic() - t0
            assert resp.status_code == 200
            assert elapsed < 0.9  # did not ride the slow replica's 300 ms x queue
            assert router.hedge_counts()[HEDGE_OUTCOME_HEDGE] == 1
            metrics = requests.get(base + "/metrics").text
            assert 'nv_fleet_hedges_total{outcome="hedge"} 1' in metrics
            assert check_exposition(metrics) == []
        finally:
            server.stop()
            slow.stop()
            fast.stop()

    def test_no_hedge_without_idempotency_key(self):
        replicas, _, router, server = _fleet(hedge_us=1_000)
        try:
            base = f"http://{server.http_address}"
            resp = requests.post(
                base + "/v2/models/fleet_device/infer", json=_infer_body()
            )
            assert resp.status_code == 200
            assert sum(router.hedge_counts().values()) == 0
        finally:
            _teardown_fleet(replicas, server)


# --------------------------------------------------------------------------- #
# integration: admin-state replay on rejoin                                   #
# --------------------------------------------------------------------------- #


class TestAdminReplay:
    def test_crashed_replica_rejoins_with_shm_state(self):
        """Register a system-shm AND a tpu-shm region through the
        router, crash+restart one replica (same ports), and assert the
        rejoined replica serves a shm-routed infer WITHOUT the client
        re-registering anything."""
        import tritonclient_tpu.utils.shared_memory as shm
        import tritonclient_tpu.utils.tpu_shared_memory as tpushm

        replicas, replica_set, router, server = _fleet()
        region = tpu_region = None
        try:
            region = shm.create_shared_memory_region(
                "chaos_in", "/chaos_replay_in", 64
            )
            tpu_region = tpushm.create_shared_memory_region("chaos_tpu", 64)
            base = f"http://{server.http_address}"
            shm.set_shared_memory_region(
                region, [np.arange(16, dtype=np.int32).reshape(1, 16)]
            )
            # Through the ROUTER: fan-out + journal.
            assert requests.post(
                base + "/" + shm_admin_path("system", "register", "chaos_in"),
                json={"key": "/chaos_replay_in", "offset": 0,
                      "byte_size": 64},
            ).status_code == 200
            import base64 as b64

            assert requests.post(
                base + "/" + shm_admin_path("tpu", "register", "chaos_tpu"),
                json={
                    "raw_handle": {"b64": b64.b64encode(
                        tpushm.get_raw_handle(tpu_region)
                    ).decode()},
                    "device_id": 0, "byte_size": 64,
                },
            ).status_code == 200
            # Register-then-unregister: replay must converge to ABSENT.
            assert requests.post(
                base + "/" + shm_admin_path("system", "register", "gone"),
                json={"key": "/chaos_replay_in", "offset": 0,
                      "byte_size": 64},
            ).status_code == 200
            assert requests.post(
                base + "/" + shm_admin_path("system", "unregister", "gone"),
                json={},
            ).status_code == 200
            assert len(router.admin_journal()) == 4

            # Crash r0 and restart it on the SAME ports, state empty.
            old = replicas[0]
            http_port = int(old.http_address.rsplit(":", 1)[1])
            grpc_port = int(old.grpc_address.rsplit(":", 1)[1])
            old.stop()
            replica_set.probe_once()  # observe the crash
            assert replica_set.get("r0").needs_replay
            replicas[0] = InferenceServer(
                models=[FleetDeviceModel(service_ms=SERVICE_MS)],
                http_port=http_port, grpc_port=grpc_port,
            ).start()
            replica_set.probe_once()  # rejoin: replay runs here
            r0 = replica_set.get("r0")
            assert r0.state == ReplicaState.READY
            assert not r0.needs_replay
            assert r0.restarts == 1

            # The rejoined replica serves a shm-routed infer directly —
            # the client never re-registered.
            status, body = http_call(
                replicas[0].http_address, "POST",
                "v2/models/fleet_device/infer",
                body=json.dumps(_infer_body(shm_region="chaos_in")).encode(),
                headers={"Content-Type": "application/json"},
            )
            assert status == 200, body
            out = json.loads(body)["outputs"][0]
            assert out["data"][:3] == [0, 1, 2]
            # tpu region present; unregistered region absent.
            status, body = http_call(
                replicas[0].http_address, "GET",
                shm_admin_path("tpu", "status"),
            )
            assert status == 200
            assert any(r["name"] == "chaos_tpu" for r in json.loads(body))
            status, body = http_call(
                replicas[0].http_address, "GET",
                shm_admin_path("system", "status"),
            )
            assert all(r["name"] != "gone" for r in json.loads(body))
            metrics = requests.get(base + "/metrics").text
            assert (
                'nv_fleet_replica_restarts_total{replica="r0"} 1' in metrics
            )
            assert check_exposition(metrics) == []
            # Lifecycle discipline (witnessed by tpusan): unregister from
            # every replica (fan-out) before destroying the handles.
            assert requests.post(
                base + "/" + shm_admin_path(
                    "system", "unregister", "chaos_in"
                ), json={},
            ).status_code == 200
            assert requests.post(
                base + "/" + shm_admin_path(
                    "tpu", "unregister", "chaos_tpu"
                ), json={},
            ).status_code == 200
        finally:
            if region is not None:
                shm.destroy_shared_memory_region(region)
            if tpu_region is not None:
                tpushm.destroy_shared_memory_region(tpu_region)
            _teardown_fleet(replicas, server)


# --------------------------------------------------------------------------- #
# integration: sticky-stream resume                                           #
# --------------------------------------------------------------------------- #


class TestStreamResume:
    def test_stream_resumes_on_survivor(self):
        """Kill the replica a sticky stream is pinned to; subsequent
        stream requests flow on the survivor (rendezvous remap)."""
        import queue as queue_module

        replicas, replica_set, router, server = _fleet()
        try:
            # Find an affinity key that pins to r0 so we know the victim.
            candidates = replica_set.routable()
            key = next(
                f"stream-{i}" for i in range(64)
                if affinity_select(candidates, f"stream-{i}").name == "r0"
            )
            channel = grpc.insecure_channel(server.grpc_address)
            stub = GRPCInferenceServiceStub(channel)
            outbound: "queue_module.Queue" = queue_module.Queue()

            def request_iter():
                while True:
                    item = outbound.get()
                    if item is None:
                        return
                    yield item

            call = stub.ModelStreamInfer(
                request_iter(),
                metadata=(
                    ("stream-affinity-key", key),
                    (HEADER_IDEMPOTENCY_KEY, "stream"),
                ),
            )
            try:
                outbound.put(_grpc_request())
                first = next(call)
                assert first.infer_response.model_name == "fleet_device"
                # Crash the pinned replica, then keep streaming.
                replicas[0].stop()
                for i in range(3):
                    outbound.put(_grpc_request())
                    reply = next(call)
                    assert reply.infer_response.model_name == "fleet_device"
            finally:
                outbound.put(None)
                call.cancel()
                channel.close()
        finally:
            _teardown_fleet(replicas, server)


# --------------------------------------------------------------------------- #
# perf_analyzer: resilience columns + --chaos                                 #
# --------------------------------------------------------------------------- #


class TestPerfAnalyzerResilience:
    def test_retries_column_under_chaos(self, server):
        from tritonclient_tpu.perf_analyzer import PerfAnalyzer

        analyzer = PerfAnalyzer(
            url=server.http_address,
            model_name="fleet_device",
            protocol="http",
            measurement_interval_s=0.8,
            warmup_s=0.0,
            collect_server_stats=False,
            retry_attempts=3,
            chaos_plan="http.connect=refused@every=10",
            chaos_seed=11,
        )
        try:
            window = analyzer.measure(2)
            summary = window.summary()
            assert summary["errors"] == 0
            assert summary["retries"] >= 1
            assert "breaker_open" in summary and "hedge_wins" in summary
        finally:
            chaos.disable()

    def test_hedge_wins_column(self, server):
        from tritonclient_tpu.perf_analyzer import PerfAnalyzer

        analyzer = PerfAnalyzer(
            url=server.http_address,
            model_name="fleet_device",
            protocol="http",
            measurement_interval_s=0.6,
            warmup_s=0.0,
            collect_server_stats=False,
            hedge_us=1,  # hedge virtually every request
        )
        window = analyzer.measure(1)
        summary = window.summary()
        assert summary["errors"] == 0
        assert summary["count"] > 0
        assert summary["hedge_wins"] >= 0  # column present and sane

    def test_hedge_validation(self):
        from tritonclient_tpu.perf_analyzer import PerfAnalyzer

        with pytest.raises(ValueError):
            PerfAnalyzer(url="h:1", model_name="m", protocol="grpc",
                         hedge_us=10)


# --------------------------------------------------------------------------- #
# exposition checker: violation cases for the new families                    #
# --------------------------------------------------------------------------- #


class TestResilienceExpositionChecker:
    HEAD = (
        "# HELP nv_client_retries_total x\n"
        "# TYPE nv_client_retries_total counter\n"
        "# HELP nv_fleet_hedges_total x\n"
        "# TYPE nv_fleet_hedges_total counter\n"
        "# HELP nv_client_breaker_state x\n"
        "# TYPE nv_client_breaker_state gauge\n"
        "# HELP nv_fleet_replica_restarts_total x\n"
        "# TYPE nv_fleet_replica_restarts_total counter\n"
    )

    def _good_rows(self):
        rows = [
            f'nv_client_retries_total{{reason="{r}"}} 0'
            for r in ("connect", "send", "status", "idempotent")
        ]
        rows += [
            f'nv_fleet_hedges_total{{outcome="{o}"}} 0'
            for o in ("primary", "hedge", "failed")
        ]
        rows.append('nv_client_breaker_state{endpoint="r0"} 2')
        rows.append('nv_fleet_replica_restarts_total{replica="r0"} 1')
        return rows

    def test_good_document_passes(self):
        text = self.HEAD + "\n".join(self._good_rows()) + "\n"
        assert check_exposition(text) == []

    def test_noncanonical_retry_reason(self):
        rows = self._good_rows()
        rows[0] = 'nv_client_retries_total{reason="vibes"} 0'
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("vibes" in e for e in errors)

    def test_missing_hedge_outcome_row(self):
        rows = [r for r in self._good_rows() if 'outcome="failed"' not in r]
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("missing outcome rows" in e for e in errors)

    def test_breaker_value_out_of_encoding(self):
        rows = self._good_rows()
        rows = [
            r.replace('breaker_state{endpoint="r0"} 2',
                      'breaker_state{endpoint="r0"} 3')
            for r in rows
        ]
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("not in {0, 1, 2}" in e for e in errors)

    def test_restarts_label_set(self):
        rows = self._good_rows()
        rows.append('nv_fleet_replica_restarts_total{pod="x"} 0')
        errors = check_exposition(self.HEAD + "\n".join(rows) + "\n")
        assert any("label set" in e for e in errors)


# --------------------------------------------------------------------------- #
# acceptance: the crash drill (CHAOS_r01.json)                                #
# --------------------------------------------------------------------------- #


class TestChaosAcceptance:
    def test_sigkill_failover_resume_rejoin(self, tmp_path):
        """2 replica subprocesses under sustained idempotent load;
        SIGKILL one mid-stream. Assert: ejected within the probe
        window, zero client-visible failures for idempotent unary
        traffic (>= 99% availability gate), the sticky stream resumes
        on the survivor, and the restarted replica rejoins with the
        router's journaled admin state replayed. Writes the CHAOS_r01
        record (seed-deterministic fault counts) under tmp_path: a test
        run must not rewrite a tracked file."""
        import tritonclient_tpu.utils.shared_memory as shm
        from tritonclient_tpu.http import (
            InferenceServerClient as HttpClient,
            InferInput,
        )

        seed = chaos.env_seed(42)
        probe_interval_s, eject_after = 0.1, 2
        served, failures = [0], []
        lock = threading.Lock()
        stream_replies = [0]
        record = {
            "tool": "tpuchaos", "scenario": "sigkill_failover", "seed": seed,
        }
        # Client-site faults on top of the kill: nth-triggered rules so
        # the injected count is plan-determined (seed-deterministic),
        # not timing-determined.
        plan = "http.response=reset@nth=5; http.connect=refused@nth=9"
        with ChaosController() as controller, chaos.session(seed, plan):
            r0 = controller.spawn("r0", service_ms=5)
            r1 = controller.spawn("r1", service_ms=5)
            controller.wait_ready("r0")
            controller.wait_ready("r1")
            replica_set = ReplicaSet(
                probe_interval_s=probe_interval_s, eject_after=eject_after,
                backoff_base_s=0.2, probe_timeout_s=1.0,
            )
            router = FleetRouter(replicas=replica_set)
            for proc in (r0, r1):
                router.add_replica(
                    proc.name, proc.http_address, proc.grpc_address
                )
            replica_set.probe_once()
            server = FleetServer(router)
            server.start()
            replica_set.start()
            base = f"http://{server.http_address}"

            # Journaled admin state: a system-shm registration.
            region = shm.create_shared_memory_region(
                "accept_in", "/chaos_accept_in", 64
            )
            try:
                shm.set_shared_memory_region(
                    region, [np.arange(16, dtype=np.int32).reshape(1, 16)]
                )
                assert requests.post(
                    base + "/" + shm_admin_path(
                        "system", "register", "accept_in"
                    ),
                    json={"key": "/chaos_accept_in", "offset": 0,
                          "byte_size": 64},
                ).status_code == 200

                # Sustained idempotent unary load through OUR client (the
                # chaos choke points + RetryPolicy live there).
                stop = threading.Event()

                def worker(wid):
                    policy = RetryPolicy(max_attempts=4, base_delay_s=0.02,
                                         rng=random.Random(seed + wid))
                    client = HttpClient(server.http_address,
                                        retry_policy=policy)
                    inputs = [InferInput("INPUT", [1, 16], "INT32")]
                    inputs[0].set_data_from_numpy(
                        np.arange(16, dtype=np.int32).reshape(1, 16)
                    )
                    i = 0
                    while not stop.is_set():
                        i += 1
                        try:
                            client.infer(
                                "fleet_device", inputs,
                                idempotency_key=f"w{wid}-{i}",
                            )
                            with lock:
                                served[0] += 1
                        except Exception as e:  # noqa: BLE001
                            with lock:
                                failures.append(repr(e))
                    client.close()

                threads = [
                    threading.Thread(target=worker, args=(w,), daemon=True)
                    for w in range(3)
                ]
                for t in threads:
                    t.start()

                # A sticky stream pinned to the victim (r0).
                candidates = replica_set.routable()
                key = next(
                    f"s-{i}" for i in range(128)
                    if affinity_select(candidates, f"s-{i}").name == "r0"
                )
                import queue as queue_module

                outbound: "queue_module.Queue" = queue_module.Queue()

                def request_iter():
                    while True:
                        item = outbound.get()
                        if item is None:
                            return
                        yield item

                channel = grpc.insecure_channel(server.grpc_address)
                stub = GRPCInferenceServiceStub(channel)
                call = stub.ModelStreamInfer(
                    request_iter(),
                    metadata=(
                        ("stream-affinity-key", key),
                        (HEADER_IDEMPOTENCY_KEY, "stream"),
                    ),
                )
                outbound.put(_grpc_request())
                assert next(call).infer_response.model_name == "fleet_device"
                stream_replies[0] += 1

                time.sleep(0.6)  # tpulint: disable=TPU001 (live-load window)

                # ---- the crash ------------------------------------------------
                kill_at = time.monotonic()
                controller.sigkill("r0")
                ejected_in = _eventually(
                    lambda: (
                        replica_set.get("r0").state == ReplicaState.EJECTED
                        and time.monotonic() - kill_at
                    ),
                    timeout_s=(eject_after + 3) * probe_interval_s + 3.0,
                )
                assert ejected_in, "router never ejected the killed replica"
                record["ejected_within_s"] = round(float(ejected_in), 3)

                # Stream resumes on the survivor.
                for _ in range(3):
                    outbound.put(_grpc_request())
                    reply = next(call)
                    assert reply.infer_response.model_name == "fleet_device"
                    stream_replies[0] += 1

                time.sleep(0.6)  # tpulint: disable=TPU001 (failover window)

                # ---- restart + rejoin ----------------------------------------
                controller.restart("r0")
                rejoined = _eventually(
                    lambda: replica_set.get("r0").state == ReplicaState.READY,
                    timeout_s=15.0,
                )
                assert rejoined, "restarted replica never rejoined"
                assert replica_set.get("r0").restarts == 1
                # Admin state replayed: the rejoined PROCESS serves a
                # shm-routed infer without any client re-registration.
                status, body = http_call(
                    controller.get("r0").http_address, "POST",
                    "v2/models/fleet_device/infer",
                    body=json.dumps(
                        _infer_body(shm_region="accept_in")
                    ).encode(),
                    headers={"Content-Type": "application/json"},
                )
                assert status == 200, body
                record["admin_replayed"] = True

                time.sleep(0.4)  # tpulint: disable=TPU001 (rebalance window)
                stop.set()
                for t in threads:
                    t.join(timeout=15)
                outbound.put(None)
                call.cancel()
                channel.close()

                metrics = requests.get(base + "/metrics").text
                assert check_exposition(metrics) == []
                assert (
                    'nv_fleet_replica_restarts_total{replica="r0"} 1' in metrics
                )
                summary = chaos.summary()
                replica_set.stop()
                server.stop()
            finally:
                shm.destroy_shared_memory_region(region)

        # ---- the recorded artifact ---------------------------------------
        total = served[0] + len(failures)
        availability = served[0] / total if total else 0.0
        record.update({
            "plan": plan,
            "faults_injected": summary["injected"],
            "faults_survived": summary["survived"],
            "by_site": summary["by_site"],
            "unary_served": served[0],
            "unary_failures": len(failures),
            "availability_idempotent": round(availability, 5),
            "stream_replies_across_crash": stream_replies[0],
            "stream_resumed": stream_replies[0] >= 4,
            "pass": bool(availability >= 0.99),
        })
        with open(tmp_path / "CHAOS_r01.json", "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        # Deterministic, plan-determined fault set: both nth rules fired
        # and were survived by retries, plus the controller's SIGKILL.
        assert summary["injected"] == 3
        assert summary["by_site"]["http.response"]["survived"] == 1
        assert summary["by_site"]["http.connect"]["survived"] == 1
        assert summary["by_site"]["replica.r0"]["injected"] == 1
        assert stream_replies[0] >= 4
        assert availability >= 0.99, failures[:5]
        assert failures == []  # idempotent traffic: ZERO visible failures
