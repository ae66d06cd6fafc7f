"""Serving frontend (``trlx_tpu/serve/``, docs/SERVING.md).

The load-bearing contracts, each pinned here:

- **streaming parity** — the concatenation of SSE stream deltas plus the
  harvest tail is bit-identical to the full unary result, which is
  bit-identical to a solo ``generate`` with the same seed at the engine's
  padded width;
- **multi-tenant isolation** — byte-identical prompts under two tenants
  build disjoint prefix chains (tenant B never hits tenant A's blocks),
  and a quota'd tenant's overflow fails onto ``engine.failed`` without
  touching other tenants' work;
- **host-RAM tiering** — prefix blocks evicted device-side re-land from
  the host pool bit-identically to a cold prefill, across block sizes;
- **priority scheduling** — interactive-class arrivals preempt
  still-prefilling batch traffic at step boundaries, and ``reserve_slots``
  holds capacity that batch classes can never take;
- **SLO-aware admission** — 429 only on provable evidence (hard queue cap
  or EWMA-predicted wait past the class SLO), 503 exactly while draining;
- **serve-while-training** — PPO ``learn()`` answers a concurrent
  streaming HTTP request mid-training, single-params-version, reproducible
  by a solo ``generate`` under the retained version's params.
"""

import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trlx_tpu.data.configs import ModelConfig
from trlx_tpu.engine.core import ContinuousEngine
from trlx_tpu.models.builder import build_causal_lm
from trlx_tpu.models.transformer import make_kv_cache
from trlx_tpu.ops.paged_kv import PagedSpec, num_table_blocks
from trlx_tpu.ops.sampling import GenerationConfig, generate, per_row_keys
from trlx_tpu.ops.slot_refill import make_slot_refill_fns
from trlx_tpu.resilience.faults import FaultPlan, poll_fault
from trlx_tpu.serve.request import ServeRequest
from trlx_tpu.serve.scheduler import AdmissionController
from trlx_tpu.serve.server import ServeServer
from trlx_tpu.serve.tiering import HostTier

_EOS = 3
_PAD = 258
_B, _P, _N = 2, 10, 9  # P not divisible by block sizes 3, 4


@pytest.fixture(scope="module")
def tiny_lm():
    module, params, tcfg = build_causal_lm(
        ModelConfig(model_path="builtin:gpt2-test"), head="value"
    )

    def apply_fn(p, ids, **kw):
        return module.apply({"params": p}, ids, **kw)

    return apply_fn, params, tcfg


def _eos_boost(step_out, logits):
    # heterogeneous response lengths (same knob as tests/test_engine.py)
    return logits.at[..., _EOS].add(4.0)


def _gen_config(**kw):
    base = dict(
        max_new_tokens=_N, eos_token_id=_EOS, pad_token_id=_PAD,
        min_new_tokens=2, per_row_rng=True,
    )
    base.update(kw)
    return GenerationConfig(**base)


def _prompt(seed, P=_P):
    rs = np.random.RandomState(seed)
    ids = rs.randint(0, 200, (P,)).astype(np.int32)
    return ids, np.ones_like(ids)


def _keys(seed):
    """The serve pump's per-request RNG chain (server.py _request_keys)."""
    return np.asarray(per_row_keys(jax.random.PRNGKey(seed), 1))


_SOLO_CACHE = {}


def _solo(tiny_lm, ids, mask, seed):
    """B=1 solo ``generate`` with the serve pump's key derivation — the
    masked response every serving path must reproduce bit-for-bit."""
    key = (ids.tobytes(), mask.tobytes(), seed)
    if key in _SOLO_CACHE:
        return _SOLO_CACHE[key]
    apply_fn, params, tcfg = tiny_lm
    out = generate(
        apply_fn, params, lambda b, s: make_kv_cache(tcfg, b, s),
        jnp.asarray(ids[None]), jnp.asarray(mask[None]),
        jax.random.PRNGKey(seed), _gen_config(), adjust_logits=_eos_boost,
    )
    masked = np.asarray(out.response_tokens[0])[
        np.asarray(out.response_mask[0]) == 1
    ]
    _SOLO_CACHE[key] = masked
    return masked


_FNS_CACHE = {}


def _engine(tiny_lm, B=_B, block_size=4, prefix=False, capacity=0,
            prefill_chunk=0, segment_len=3, max_blocks=0):
    apply_fn, params, tcfg = tiny_lm
    paged = PagedSpec(
        block_size=block_size,
        max_blocks=max_blocks
        or 1 + 2 * B * num_table_blocks(_P + _N, block_size) + 8,
    )
    fkey = (B, paged, segment_len)
    fns = _FNS_CACHE.get(fkey)
    if fns is None:
        fns = make_slot_refill_fns(
            apply_fn, lambda b, s: make_kv_cache(tcfg, b, s), B, _P,
            _gen_config(), adjust_logits=_eos_boost, segment_len=segment_len,
            params_example=params, paged=paged,
        )
        _FNS_CACHE[fkey] = fns
    return ContinuousEngine(
        fns, params, _PAD, prefix_cache=prefix,
        prefix_capacity_blocks=capacity, prefill_chunk=prefill_chunk,
    )


def _drain_engine(engine, limit=500):
    got = []
    for _ in range(limit):
        if not engine.busy:
            break
        got.extend(engine.step())
    return got


def _serve_threads():
    return [
        t for t in threading.enumerate()
        if t.name.startswith("trlx-serve") and t.is_alive()
    ]


# ---------------------------------------------------------------------------
# request / admission / fault-kind units
# ---------------------------------------------------------------------------


def _req(stream=True, max_buffered=64):
    ids = np.arange(4, dtype=np.int32)
    return ServeRequest(
        rid=1, prompt_ids=ids, prompt_mask=np.ones_like(ids),
        tenant="t", klass="interactive", seed=0, stream=stream,
        max_buffered=max_buffered,
    )


class TestServeRequest:
    def test_event_sequencing_and_terminal(self):
        r = _req()
        r.mark_generating(params_version=7)
        assert r.push_tokens(np.array([1, 2], np.int32))
        r.finish(np.array([1, 2, 3], np.int32), queue_wait_s=0.01)
        kind, payload = r.next_event()
        assert kind == "tokens" and payload.tolist() == [1, 2]
        kind, payload = r.next_event()
        assert kind == "done" and payload.tolist() == [1, 2, 3]
        assert r.wait_done(timeout=1.0) == "DONE"
        snap = r.snapshot()
        assert snap["params_version"] == 7 and snap["n_tokens"] == 3
        # terminal states are sticky
        r.fail("late")
        assert r.snapshot()["state"] == "DONE"

    def test_stream_buffer_bound_drops_slow_client(self):
        r = _req(max_buffered=2)
        assert r.push_tokens(np.array([1], np.int32))
        assert r.push_tokens(np.array([2], np.int32))
        # third undelivered chunk crosses the bound: producer told to stop
        assert not r.push_tokens(np.array([3], np.int32))
        kind, msg = r.next_event()
        assert kind == "dropped" and "stream" in msg
        # a later finish() must not resurrect the request
        r.finish(np.array([1, 2, 3], np.int32), 0.0)
        assert r.snapshot()["state"] == "DROPPED"

    def test_fail_clears_buffered_chunks(self):
        r = _req()
        r.push_tokens(np.array([1], np.int32))
        r.fail("quota")
        kind, msg = r.next_event()
        assert kind == "failed" and msg == "quota"


class TestAdmission:
    def test_unknown_class_rejected_400(self):
        a = AdmissionController(slots=2)
        d = a.try_admit("vip")
        assert not d.admitted and d.status == 400

    def test_hard_queue_cap_429_with_retry_after(self):
        a = AdmissionController(slots=1, max_queue=3)
        for _ in range(3):
            assert a.try_admit("actor").admitted
        d = a.try_admit("actor")
        assert not d.admitted and d.status == 429
        assert d.retry_after_s > 0 and "queue full" in d.reason
        a.release("actor")
        assert a.try_admit("actor").admitted

    def test_slo_rejects_only_on_ewma_evidence(self):
        a = AdmissionController(
            slots=1, slo_s={"interactive": 0.05}, max_queue=64
        )
        # queue depth alone is NOT evidence: without observed service
        # times the predicted wait is unknowable, so requests admit
        for _ in range(8):
            assert a.try_admit("interactive").admitted
        # observed ~1s services make the predicted wait provably blown
        for _ in range(5):
            a.note_service(1.0)
        d = a.try_admit("interactive")
        assert not d.admitted and d.status == 429
        assert d.retry_after_s >= 1.0

    def test_draining_503(self):
        a = AdmissionController(slots=2)
        a.set_draining()
        d = a.try_admit("interactive")
        assert not d.admitted and d.status == 503
        assert a.snapshot()["drain_rejected"] == 1


class TestServeFaultKinds:
    def test_slow_client_triggers_on_request_index(self):
        plan = FaultPlan.parse("slow_client@request:2")
        assert not plan.poll("slow_client", request=1)
        assert plan.poll("slow_client", request=2)
        assert plan.fired["slow_client"] == 1

    def test_request_flood_on_step(self):
        plan = FaultPlan.parse("request_flood@step:3")
        assert not plan.poll("request_flood", step=2)
        assert plan.poll("request_flood", step=3)

    def test_module_level_poll_fault_request(self):
        from trlx_tpu.resilience.faults import set_active_plan

        set_active_plan(FaultPlan.parse("slow_client@request:1"))
        try:
            assert poll_fault("slow_client", request=1)
            assert not poll_fault("slow_client", request=2)
        finally:
            set_active_plan(None)


# ---------------------------------------------------------------------------
# multi-tenant isolation + quotas (engine level)
# ---------------------------------------------------------------------------


class TestTenantIsolation:
    def test_cross_tenant_prompts_never_share_prefix_blocks(self, tiny_lm):
        engine = _engine(tiny_lm, prefix=True)
        ids, mask = _prompt(1)
        for wave, (tenant, want_hits) in enumerate(
            [("a", False), ("a", True), ("b", False), ("b", True)]
        ):
            before = engine.stats.prefix_hit_blocks
            engine.enqueue_prompts(
                ids[None], mask[None], _keys(5), tenant=tenant,
                klass="interactive",
            )
            got = _drain_engine(engine)
            assert len(got) == 1
            # identical bits regardless of tenant or hit path
            np.testing.assert_array_equal(
                got[0].tokens[got[0].mask == 1], _solo(tiny_lm, ids, mask, 5),
                err_msg=f"wave {wave} tenant {tenant}",
            )
            hits = engine.stats.prefix_hit_blocks - before
            if want_hits:
                assert hits > 0, f"same-tenant resubmit (wave {wave}) missed"
            else:
                # first contact under this tenant: byte-identical prompt,
                # yet ZERO blocks shared with the other tenant's chain
                assert hits == 0, f"cross-tenant hit leaked (wave {wave})"

    def test_tenant_quota_fails_onto_failed_deque(self, tiny_lm):
        engine = _engine(tiny_lm, prefix=True)
        engine.allocator.set_tenant_quota("small", 1)  # prompt needs 3+
        ids, mask = _prompt(2)
        meta = {"rid": 42}
        engine.enqueue_prompts(
            ids[None], mask[None], _keys(0), metas=[meta], tenant="small"
        )
        engine.step()
        assert len(engine.failed) == 1
        failed_req, err = engine.failed.popleft()
        assert failed_req.meta is meta
        assert "quota" in err
        assert not engine.busy  # the slot was not wedged
        # an unquota'd tenant is untouched by the failure
        engine.enqueue_prompts(ids[None], mask[None], _keys(0), tenant=None)
        got = _drain_engine(engine)
        assert len(got) == 1
        np.testing.assert_array_equal(
            got[0].tokens[got[0].mask == 1], _solo(tiny_lm, ids, mask, 0)
        )


# ---------------------------------------------------------------------------
# host-RAM KV tiering
# ---------------------------------------------------------------------------


class TestHostTier:
    @pytest.mark.parametrize("block_size", [3, 4])
    def test_reland_bit_identical_to_cold_prefill(self, tiny_lm, block_size):
        n_full = (_P - 1) // block_size  # committed full prompt blocks
        engine = _engine(
            tiny_lm, block_size=block_size, prefix=True, capacity=n_full
        )
        tier = HostTier(max_blocks=64, block_bytes=1)
        engine.attach_host_tier(tier)
        ids_a, mask_a = _prompt(3)
        ids_b, mask_b = _prompt(4)
        cold = {}
        # wave 1: A inserts its chain; wave 2: B's insert evicts A past the
        # capacity cap — the eviction hook spills A's block KV host-side
        for seed, (ids, mask) in [(7, (ids_a, mask_a)), (8, (ids_b, mask_b))]:
            engine.enqueue_prompts(ids[None], mask[None], _keys(seed))
            (c,) = _drain_engine(engine)
            cold[seed] = c.tokens[c.mask == 1]
            np.testing.assert_array_equal(
                cold[seed], _solo(tiny_lm, ids, mask, seed)
            )
        snap = tier.snapshot()
        assert snap["spilled"] > 0, "eviction never spilled to the host tier"
        # wave 3: A again — device chain is gone, host chunks re-land
        before = engine.stats.host_tier_hit_blocks
        engine.enqueue_prompts(ids_a[None], mask_a[None], _keys(7))
        (c,) = _drain_engine(engine)
        relanded = engine.stats.host_tier_hit_blocks - before
        assert relanded > 0, "re-submit did not re-land from the host tier"
        np.testing.assert_array_equal(c.tokens[c.mask == 1], cold[7])
        assert engine.stats.host_tier_tokens_saved >= relanded * block_size
        assert tier.snapshot()["relanded"] >= relanded

    def test_tier_flushes_on_params_change(self, tiny_lm):
        engine = _engine(tiny_lm, prefix=True, capacity=2)
        tier = HostTier(max_blocks=64)
        engine.attach_host_tier(tier)
        ids, mask = _prompt(5)
        for seed in (1, 2):
            p, m = _prompt(seed + 10)
            engine.enqueue_prompts(p[None], m[None], _keys(seed))
            _drain_engine(engine)
        assert len(tier) > 0
        # stale spilled KV is invalid under new params — must clear
        fresh = jax.tree_util.tree_map(jnp.copy, engine.params)
        engine.swap_params(fresh, version=99)
        assert len(tier) == 0


# ---------------------------------------------------------------------------
# priority scheduling: preemption + reserved slots (engine level)
# ---------------------------------------------------------------------------


class TestPriorityScheduling:
    def test_interactive_preempts_prefilling_actor_slots(self, tiny_lm):
        # chunked prefill (4-col spans over P=10) keeps slots in the
        # still-prefilling, cheaply-vacated state across steps
        engine = _engine(tiny_lm, prefill_chunk=4)
        prompts = [_prompt(10 + i) for i in range(3)]
        for i, (ids, mask) in enumerate(prompts):
            engine.enqueue_prompts(
                ids[None], mask[None], _keys(20 + i), metas=[f"actor{i}"],
                klass="actor",
            )
        engine.step()  # both slots now mid-prefill on actor work
        iids, imask = _prompt(30)
        engine.enqueue_prompts(
            iids[None], imask[None], _keys(30), metas=["vip"],
            klass="interactive",
        )
        order = [c.meta for c in _drain_engine(engine)]
        assert engine.stats.preempted_rows >= 1
        assert set(order) == {"actor0", "actor1", "actor2", "vip"}
        # the interactive request jumped the saturating batch: it cannot
        # finish last (bit-exactness of the preempted rows is pinned by
        # test_preempted_rows_reproduce_solo_bits)
        assert order.index("vip") < len(order) - 1

    def test_preempted_rows_reproduce_solo_bits(self, tiny_lm):
        engine = _engine(tiny_lm, prefill_chunk=4, prefix=True)
        prompts = {f"actor{i}": (_prompt(40 + i), 50 + i) for i in range(3)}
        for name, ((ids, mask), seed) in prompts.items():
            engine.enqueue_prompts(
                ids[None], mask[None], _keys(seed), metas=[name], klass="actor"
            )
        engine.step()
        (iids, imask) = _prompt(60)
        engine.enqueue_prompts(
            iids[None], imask[None], _keys(61), metas=["vip"],
            klass="interactive",
        )
        got = {c.meta: c for c in _drain_engine(engine)}
        assert engine.stats.preempted_rows >= 1
        for name, ((ids, mask), seed) in prompts.items():
            np.testing.assert_array_equal(
                got[name].tokens[got[name].mask == 1],
                _solo(tiny_lm, ids, mask, seed), err_msg=name,
            )
        np.testing.assert_array_equal(
            got["vip"].tokens[got["vip"].mask == 1],
            _solo(tiny_lm, iids, imask, 61),
        )

    def test_reserve_slots_held_for_interactive(self, tiny_lm):
        engine = _engine(tiny_lm)
        engine.reserve_slots = 1
        ids, mask = _prompt(9)
        for i in range(2):
            engine.enqueue_prompts(
                ids[None], mask[None], _keys(70 + i), metas=[f"a{i}"],
                klass="actor",
            )
        done = engine.step()
        assert engine.live == 1, "actor traffic took the reserved slot"
        assert engine.pending == 1
        engine.enqueue_prompts(
            ids[None], mask[None], _keys(72), metas=["vip"],
            klass="interactive",
        )
        done += engine.step()
        # interactive admitted instantly, into the reserved slot, while the
        # second actor request still waits. `live` would not say so: a step
        # ends with a harvest, and a0 (5 tokens under this seed, segments of
        # 3) finishes inside this very step and leaves its slot free
        assert "vip" in {meta for _, meta, _ in engine.progress_snapshot()}
        assert engine.pending == 1
        got = {c.meta for c in done + _drain_engine(engine)}
        assert got == {"a0", "a1", "vip"}


# ---------------------------------------------------------------------------
# ServeServer (pump thread, no HTTP)
# ---------------------------------------------------------------------------


class TestServeServer:
    def test_requires_paged_backend(self, tiny_lm):
        class Dense:
            spec = None

        with pytest.raises(ValueError, match="paged"):
            ServeServer(Dense())

    def test_streaming_parity_and_unary(self, tiny_lm):
        srv = ServeServer(_engine(tiny_lm))
        srv.start()
        try:
            ids, mask = _prompt(21)
            solo = _solo(tiny_lm, ids, mask, 13)
            req, rej = srv.submit(ids, mask, seed=13, stream=True)
            assert rej is None
            deltas, done = [], None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                kind, payload = req.next_event(timeout=0.2)
                if kind == "tokens":
                    deltas.append(payload)
                elif kind == "done":
                    done = payload
                    break
                elif kind in ("failed", "dropped"):
                    pytest.fail(f"request {kind}: {payload}")
            assert done is not None
            streamed = (
                np.concatenate(deltas) if deltas else np.zeros(0, np.int32)
            )
            # stream deltas + harvest tail ARE the unary result, which is
            # the solo generate's masked response
            np.testing.assert_array_equal(streamed, done)
            np.testing.assert_array_equal(done, solo)
            # unary path, same seed: byte-identical again
            req2, rej2 = srv.submit(ids, mask, seed=13, stream=False)
            assert rej2 is None and req2.wait_done(60) == "DONE"
            np.testing.assert_array_equal(req2.result_tokens, solo)
            flat = srv.flat_metrics()
            assert flat["serve/completed"] == 2
            assert flat["serve/active"] == 0
            assert flat["serve/ttft_p95"] > 0
            detail = srv.detail_metrics()
            assert "default/interactive" in detail["tenants"]
        finally:
            srv.close()
        assert _serve_threads() == []

    def test_published_version_stamped_single_version(self, tiny_lm):
        engine = _engine(tiny_lm)
        srv = ServeServer(engine, retain_param_versions=2)
        srv.start()
        try:
            srv.publish(jax.tree_util.tree_map(jnp.copy, engine.params), 7)
            ids, mask = _prompt(22)
            req, _ = srv.submit(ids, mask, seed=1)
            assert req.wait_done(60) == "DONE"
            assert req.snapshot()["params_version"] == 7
            assert srv.params_for_version(7) is not None
            assert srv.params_for_version(6) is None
        finally:
            srv.close()

    def test_slow_client_dropped_engine_not_wedged(self, tiny_lm):
        srv = ServeServer(_engine(tiny_lm), stream_buffer=1)
        srv.start()
        try:
            ids, mask = _prompt(23)
            req, _ = srv.submit(ids, mask, seed=2, stream=True)
            # never consume: the pump's pushes cross the 1-chunk bound
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if req.snapshot()["state"] == "DROPPED":
                    break
                time.sleep(0.01)
            assert req.snapshot()["state"] == "DROPPED"
            # the slot kept decoding and the engine still serves cleanly
            req2, _ = srv.submit(ids, mask, seed=2, stream=False)
            assert req2.wait_done(60) == "DONE"
            np.testing.assert_array_equal(
                req2.result_tokens, _solo(tiny_lm, ids, mask, 2)
            )
            flat = srv.flat_metrics()
            assert flat["serve/dropped"] == 1
            assert flat["serve/completed"] == 1
            assert flat["serve/active"] == 0
        finally:
            srv.close()

    def test_flood_drill_sheds_load_via_429(self, tiny_lm):
        srv = ServeServer(_engine(tiny_lm), max_queue=4)
        srv.start()
        try:
            rejected = srv.flood_drill()
            assert rejected == 4  # 2 * max_queue probes, cap admits 4
            assert srv.flat_metrics()["serve/flood_rejected"] == 4
            # the drill released its probes: real traffic still admits
            ids, mask = _prompt(24)
            req, rej = srv.submit(ids, mask, seed=3)
            assert rej is None and req.wait_done(60) == "DONE"
        finally:
            srv.close()

    def test_drain_finishes_inflight_then_503(self, tiny_lm):
        srv = ServeServer(_engine(tiny_lm), drain_timeout_s=30.0)
        srv.start()
        ids, mask = _prompt(25)
        req, _ = srv.submit(ids, mask, seed=4)
        assert srv.drain() is True  # in-flight work finished inside window
        assert req.snapshot()["state"] == "DONE"
        np.testing.assert_array_equal(
            req.result_tokens, _solo(tiny_lm, ids, mask, 4)
        )
        _, rej = srv.submit(ids, mask, seed=4)
        assert rej is not None and rej[0] == 503
        assert _serve_threads() == []

    def test_close_fails_abandoned_requests(self, tiny_lm):
        srv = ServeServer(_engine(tiny_lm))
        srv.start()
        ids, mask = _prompt(26)
        req, _ = srv.submit(ids, mask, seed=5)
        srv.close()  # immediate stop: no handler may block forever
        state = req.wait_done(10)
        assert state in ("DONE", "FAILED")
        if state == "FAILED":
            assert "draining" in req.snapshot()["error"]
        assert srv.flat_metrics()["serve/active"] == 0
        assert _serve_threads() == []

    def test_validation_400s(self, tiny_lm):
        srv = ServeServer(_engine(tiny_lm))
        try:
            _, rej = srv.submit(np.zeros(0, np.int32))
            assert rej[0] == 400
            _, rej = srv.submit(np.zeros(_P + 5, np.int32))
            assert rej[0] == 400 and "padded width" in rej[1]
            _, rej = srv.submit(np.ones(4, np.int32), klass="vip")
            assert rej[0] == 400
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# HTTP frontend (SSE streaming over a real socket)
# ---------------------------------------------------------------------------


def _post(port, payload, path="/v1/generate", timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", path, json.dumps(payload),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read().decode()
    finally:
        conn.close()


def _get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read().decode())
    finally:
        conn.close()


def _parse_sse(body):
    toks, done = [], None
    for line in body.splitlines():
        if line.startswith("data: "):
            evt = json.loads(line[len("data: "):])
            if "tokens" in evt:
                toks.extend(evt["tokens"])
            if evt.get("done"):
                done = evt
    return toks, done


class TestHTTPFrontend:
    @pytest.fixture()
    def srv(self, tiny_lm):
        server = ServeServer(_engine(tiny_lm))
        server.start(host="127.0.0.1", port=0)
        yield server
        server.close()
        assert _serve_threads() == []

    def test_unary_and_streaming_parity_over_http(self, tiny_lm, srv):
        ids, mask = _prompt(31)
        solo = _solo(tiny_lm, ids, mask, 17)
        status, _, body = _post(
            srv.port, {"prompt_ids": ids.tolist(), "seed": 17}
        )
        assert status == 200
        payload = json.loads(body)
        assert payload["n_tokens"] == len(payload["tokens"])
        np.testing.assert_array_equal(
            np.asarray(payload["tokens"], np.int32), solo
        )
        status, _, body = _post(
            srv.port, {"prompt_ids": ids.tolist(), "seed": 17, "stream": True}
        )
        assert status == 200
        toks, done = _parse_sse(body)
        assert done is not None and done["n_tokens"] == len(toks)
        np.testing.assert_array_equal(np.asarray(toks, np.int32), solo)

    def test_health_metrics_and_errors(self, srv):
        status, health = _get(srv.port, "/healthz")
        assert status == 200 and health["status"] == "ok"
        status, _, body = _post(srv.port, {"prompt_ids": []})
        assert status == 400
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        try:
            conn.request("POST", "/v1/generate", "not json")
            assert conn.getresponse().status == 400
        finally:
            conn.close()
        ids, _ = _prompt(32)
        status, _, _ = _post(srv.port, {"prompt_ids": ids.tolist(), "seed": 1})
        assert status == 200
        status, metrics = _get(srv.port, "/metrics")
        assert status == 200
        assert metrics["serve"]["serve/completed"] >= 1
        assert "default/interactive" in metrics["tenants"]

    def test_draining_503_with_no_retry_header(self, srv):
        srv.admission.set_draining()
        status, health = _get(srv.port, "/healthz")
        assert health["status"] == "draining"
        ids, _ = _prompt(33)
        status, headers, _ = _post(srv.port, {"prompt_ids": ids.tolist()})
        assert status == 503
        assert "Retry-After" not in headers

    def test_queue_full_429_sets_retry_after(self, tiny_lm):
        server = ServeServer(_engine(tiny_lm), max_queue=1)
        server.start(host="127.0.0.1", port=0)
        try:
            # saturate the hard cap admission-side (no engine traffic)
            assert server.admission.try_admit("interactive").admitted
            ids, _ = _prompt(34)
            status, headers, body = _post(
                server.port, {"prompt_ids": ids.tolist()}
            )
            assert status == 429
            assert float(headers["Retry-After"]) > 0
            assert "queue full" in json.loads(body)["error"]
        finally:
            server.close()


# ---------------------------------------------------------------------------
# trainer integration: config validation + serve-while-training e2e
# ---------------------------------------------------------------------------


def _serve_ppo_config(tmp_path, **serve_overrides):
    from trlx_tpu.data.default_configs import default_ppo_config

    serve = dict(
        enabled=True, host="127.0.0.1", port=0, slots=2, max_new_tokens=8,
        retain_param_versions=8, drain_timeout_s=10.0,
    )
    serve.update(serve_overrides)
    return default_ppo_config().evolve(
        train=dict(
            seq_length=48, batch_size=8, total_steps=2, eval_interval=100,
            checkpoint_interval=1000, checkpoint_dir=str(tmp_path / "ckpts"),
            tracker=None, continuous_batching=True,
            continuous_batching_segment=3,
        ),
        model=dict(model_path="builtin:gpt2-test", num_layers_unfrozen=1),
        engine=dict(backend="paged", prefix_cache=True),
        method=dict(
            num_rollouts=8, chunk_size=4, ppo_epochs=1,
            gen_kwargs=dict(
                max_new_tokens=8, top_k=0, top_p=1.0, do_sample=True,
                per_row_rng=True,
            ),
        ),
        serve=serve,
    )


_PROMPTS = ["hello world", "the quick brown fox", "lorem ipsum", "foo bar"] * 4


def _letter_reward(samples, prompts, outputs, **kwargs):
    return [float(sum(c in "aeiou" for c in o)) for o in outputs]


def _build_trainer(cfg):
    import trlx_tpu.pipeline.offline_pipeline  # noqa: F401 (registration)
    import trlx_tpu.trainer.ppo  # noqa: F401 (registration)
    from trlx_tpu.pipeline import get_pipeline
    from trlx_tpu.trainer import get_trainer

    trainer = get_trainer(cfg.train.trainer)(
        config=cfg, reward_fn=_letter_reward, metric_fn=None,
        stop_sequences=[],
    )
    pipeline = get_pipeline(cfg.train.pipeline)(
        _PROMPTS, 40, trainer.tokenizer
    )
    trainer.add_prompt_pipeline(pipeline)
    trainer.add_eval_pipeline(pipeline)
    return trainer


class TestServeConfigValidation:
    def test_requires_paged_backend(self, tmp_path):
        cfg = _serve_ppo_config(tmp_path).evolve(engine=dict(backend="dense"))
        with pytest.raises(ValueError, match="paged"):
            _build_trainer(cfg)

    def test_requires_continuous_batching(self, tmp_path):
        cfg = _serve_ppo_config(tmp_path).evolve(
            train=dict(continuous_batching=False)
        )
        with pytest.raises(ValueError, match="continuous_batching"):
            _build_trainer(cfg)

    def test_reserve_slots_bounded_by_slots(self, tmp_path):
        cfg = _serve_ppo_config(tmp_path, slots=2, reserve_slots=2)
        with pytest.raises(ValueError, match="reserve_slots"):
            _build_trainer(cfg)


@pytest.mark.filterwarnings("ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_serve_while_training_e2e(tmp_path):
    """The one-binary acceptance e2e (ISSUE 19): PPO ``learn()`` serves a
    concurrent streaming HTTP request mid-training through the serving
    engine; the streamed response is bit-identical to a solo ``generate``
    under the retained params of the version stamped on the response."""
    cfg = _serve_ppo_config(tmp_path)
    trainer = _build_trainer(cfg)
    result = {}
    box = {}
    prompt = [5, 6, 7, 8, 9, 10, 11, 12]

    def client():
        deadline = time.monotonic() + 300
        srv = None
        while time.monotonic() < deadline:
            srv = getattr(trainer, "_serve", None)
            if srv is not None and srv.port:
                break
            time.sleep(0.01)
        if srv is None or not srv.port:
            result["error"] = "serving frontend never came up"
            return
        box["srv"] = srv
        try:
            status, _, body = _post(
                srv.port,
                {
                    "prompt_ids": prompt, "seed": 11, "stream": True,
                    "class": "interactive",
                },
                timeout=240,
            )
        except Exception as e:  # surfaced on the main thread below
            result["error"] = f"{type(e).__name__}: {e}"
            return
        result["status"] = status
        result["tokens"], result["done"] = _parse_sse(body)

    t = threading.Thread(target=client, name="test-serve-client")
    t.start()
    try:
        trainer.learn()
    finally:
        t.join(timeout=300)
    assert not t.is_alive(), "serve client wedged"
    assert "error" not in result, result["error"]
    assert result["status"] == 200
    done = result["done"]
    assert done is not None and done["n_tokens"] == len(result["tokens"])
    version = done["params_version"]
    assert version is not None, "response not stamped with a params version"
    srv = box["srv"]
    params = srv.params_for_version(version)
    assert params is not None, f"version {version} fell out of the history"
    # solo generate at the serve engine's padded width under the retained
    # params copy — the buffers must have survived later donated updates
    width = srv.engine.P
    ids = np.full((1, width), trainer.tokenizer.pad_token_id, np.int32)
    mask = np.zeros_like(ids)
    ids[0, -len(prompt):] = prompt
    mask[0, -len(prompt):] = 1
    out = trainer.generate(
        ids, mask, eval_mode=True, params=params,
        rng=jax.random.PRNGKey(11), max_new_tokens=8,
    )
    solo = np.asarray(out.response_tokens[0])[
        np.asarray(out.response_mask[0]) == 1
    ]
    np.testing.assert_array_equal(np.asarray(result["tokens"], np.int32), solo)
    # learn()'s finally drained serving: both serve threads are joined
    assert _serve_threads() == []
