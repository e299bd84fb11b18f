"""Multi-tenant QoS + chunked-prefill budgeting (docs/qos.md).

Coverage map:

- knob clamp tables for ``DYN_TPU_TENANT_*`` / ``DYN_TPU_PREFILL_BUDGET``
  (PR3 contract: malformed/zero/negative → defaults);
- token buckets, the LRU-bounded per-tenant rate limiter, weighted
  virtual-time fair queuing, and the prefill budget splitter;
- the admission gate's per-tenant rate shed (typed 429 with the tenant's
  OWN Retry-After) and its propagation HTTP edge → RPC header → engine
  context;
- allocator tenant block accounting + class-tiered reclaimable eviction
  (lowest class evicted first);
- the aggregated engine: weighted-fair admission, per-tenant KV budgets
  (work-conserving), and the chunked-prefill duty cycle — greedy outputs
  bitwise identical to unbudgeted prefill, interleaving bounded, with an
  unbudgeted control leg showing the full-prompt spike;
- the noisy-neighbor chaos gate (tools/qos_sim.py, virtual time): one
  abusive tenant at ~10-20x its quota moves the victim's ITL p95 < 10%
  with zero victim sheds, while the no-QoS control leg shows the real
  contention;
- zero-overhead guards: no knobs ⇒ no QoS object is ever constructed on
  the engine step loop or the admission hot path (PR5/PR6 pattern);
- telemetry: worker `tenants` dicts → cluster rollup → `dynamo_tenant_*`
  gauges (grammar-checked) → `llmctl tenant status` exit codes; mock
  worker `--tenants` drills.
"""

import asyncio
import dataclasses
from collections import OrderedDict

import pytest

from dynamo_tpu.runtime import qos as qos_mod
from dynamo_tpu.runtime.admission import (
    AdmissionController,
    AdmissionPolicy,
    OverloadedError,
)
from dynamo_tpu.runtime.qos import (
    FairQueue,
    QosPolicy,
    TenantRateLimiter,
    TokenBucket,
    env_prefill_budget,
    maybe_from_env,
    split_prefill_budget,
)


def _clear_tenant_env(monkeypatch):
    import os

    for k in list(os.environ):
        if k.startswith("DYN_TPU_TENANT_") or k == "DYN_TPU_PREFILL_BUDGET":
            monkeypatch.delenv(k, raising=False)


# -- policy / env parsing -----------------------------------------------------


class TestQosPolicyEnv:
    def test_from_env(self, monkeypatch):
        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_CLASSES", "low:1,mid:3,high:9")
        monkeypatch.setenv("DYN_TPU_TENANT_MAP", "acme=high,crawler=low")
        monkeypatch.setenv("DYN_TPU_TENANT_KEYS", "sk-1=acme,sk-2=bobco")
        monkeypatch.setenv("DYN_TPU_TENANT_DEFAULT_CLASS", "mid")
        monkeypatch.setenv("DYN_TPU_TENANT_RATE", "2.5")
        monkeypatch.setenv("DYN_TPU_TENANT_BURST", "8")
        monkeypatch.setenv("DYN_TPU_TENANT_KV_FRAC", "0.4")
        monkeypatch.setenv("DYN_TPU_TENANT_MAX", "77")
        p = QosPolicy.from_env()
        assert list(p.classes) == ["low", "mid", "high"]
        assert p.class_of("acme") == (2, 9.0)
        assert p.class_of("crawler") == (0, 1.0)
        assert p.class_of("unknown") == (1, 3.0)  # default class
        assert p.class_of(None) == (1, 3.0)
        assert p.tenant_of_key("Bearer sk-1") == "acme"
        assert p.tenant_of_key("sk-2") == "bobco"
        assert p.tenant_of_key("sk-3") is None
        assert p.rate_rps == 2.5
        assert p.burst == 8.0
        assert p.kv_frac == 0.4
        assert p.max_tenants == 77

    @pytest.mark.parametrize("bad", ["-3", "nan-ish", ""])
    def test_bad_values_clamp_to_defaults(self, monkeypatch, bad):
        """Malformed/negative knobs clamp to defaults — a bad rate must
        degrade to 'rate limiting off', never to a gate shedding 100%."""
        _clear_tenant_env(monkeypatch)
        d = QosPolicy()
        for var in ("RATE", "BURST", "KV_FRAC", "MAX"):
            monkeypatch.setenv(f"DYN_TPU_TENANT_{var}", bad)
        p = QosPolicy.from_env()
        assert p.rate_rps == d.rate_rps
        assert p.burst == d.burst
        assert p.kv_frac == d.kv_frac
        assert p.max_tenants == d.max_tenants

    def test_zero_rate_and_kv_frac_mean_disabled(self, monkeypatch):
        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_RATE", "0")
        monkeypatch.setenv("DYN_TPU_TENANT_KV_FRAC", "0")
        p = QosPolicy.from_env()
        assert p.rate_rps == 0.0 and p.kv_frac == 0.0

    def test_kv_frac_caps_at_one(self):
        assert QosPolicy(kv_frac=3.5).kv_frac == 1.0

    def test_slot_frac_clamps(self, monkeypatch):
        assert QosPolicy(slot_frac=3.5).slot_frac == 1.0
        assert QosPolicy(slot_frac=-1.0).slot_frac == 0.0
        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_SLOT_FRAC", "0.5")
        assert QosPolicy.from_env().slot_frac == 0.5
        monkeypatch.setenv("DYN_TPU_TENANT_SLOT_FRAC", "junk")
        assert QosPolicy.from_env().slot_frac == 0.0  # default: disabled

    def test_malformed_class_entries_skipped(self, monkeypatch):
        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv(
            "DYN_TPU_TENANT_CLASSES", "good:2,:9,alsogood,bad:-1,,junk:x"
        )
        p = QosPolicy.from_env()
        # bare name → weight 1; non-positive/malformed weights clamp to 1
        assert p.classes == {
            "good": 2.0, "alsogood": 1.0, "bad": 1.0, "junk": 1.0
        }

    def test_unknown_default_class_falls_back(self, monkeypatch):
        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_DEFAULT_CLASS", "nonsense")
        p = QosPolicy.from_env()
        # falls back to the LAST (highest-weight) declared class
        assert p.default_class == "premium"
        # a tenant mapped to an undeclared class also degrades safely
        p2 = QosPolicy(tenant_map={"t": "ghost"})
        assert p2.class_of("t") == p2.class_of(None)

    def test_resolve_tenant_key_map_wins_over_header(self):
        """The authenticated binding beats the client-supplied header: a
        spoofed x-tenant-id must not bill another tenant's quota."""
        p = QosPolicy(
            key_map={"sk-1": "acme"}, tenant_map={"vip": "premium"},
        )
        assert p.resolve_tenant("vip", "Bearer sk-1") == "acme"
        assert p.resolve_tenant("vip", None) == "vip"
        assert p.resolve_tenant(None, None) == qos_mod.DEFAULT_TENANT

    def test_unmapped_shared_collapses_rotating_ids(self, monkeypatch):
        """DYN_TPU_TENANT_UNMAPPED=shared: undeclared header ids share the
        default tenant's bucket — rotating a spoofed id per request
        cannot mint fresh burst tokens."""
        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_UNMAPPED", "shared")
        monkeypatch.setenv("DYN_TPU_TENANT_MAP", "vip=premium")
        p = QosPolicy.from_env()
        assert p.resolve_tenant("spoof-123", None) == qos_mod.DEFAULT_TENANT
        assert p.resolve_tenant("vip", None) == "vip"  # declared: kept
        # malformed mode degrades to per-id
        monkeypatch.setenv("DYN_TPU_TENANT_UNMAPPED", "bogus")
        assert QosPolicy.from_env().unmapped == "per-id"

    def test_maybe_from_env_gate(self, monkeypatch):
        _clear_tenant_env(monkeypatch)
        assert maybe_from_env() is None
        monkeypatch.setenv("DYN_TPU_TENANT_RATE", "1")
        assert maybe_from_env() is not None

    @pytest.mark.parametrize(
        "raw,expect", [("64", 64), ("0", 0), ("-5", 0), ("soon", 0), ("", 0)]
    )
    def test_prefill_budget_clamps(self, monkeypatch, raw, expect):
        monkeypatch.setenv("DYN_TPU_PREFILL_BUDGET", raw)
        assert env_prefill_budget() == expect


# -- token bucket / limiter ---------------------------------------------------


class TestTokenBucket:
    def test_refill_and_retry_after(self):
        b = TokenBucket(rate=2.0, capacity=2.0, now=0.0)
        assert b.take(0.0) == 0.0
        assert b.take(0.0) == 0.0
        wait = b.take(0.0)
        assert wait == pytest.approx(0.5)  # 1 token at 2/s
        # half the wait elapsed → half a token short
        assert b.take(0.25) == pytest.approx(0.25)
        assert b.take(1.0) == 0.0  # refilled

    def test_limiter_scales_by_class_weight(self):
        clock = [0.0]
        p = QosPolicy(
            tenant_map={"vip": "premium", "bulk": "batch"}, rate_rps=1.0,
            burst=1.0,
        )
        lim = TenantRateLimiter(p, clock=lambda: clock[0])
        # premium (weight 16) holds a 16-token burst; batch (weight 1) one
        vip_admitted = sum(1 for _ in range(20) if lim.take("vip") == 0.0)
        bulk_admitted = sum(1 for _ in range(20) if lim.take("bulk") == 0.0)
        assert vip_admitted == 16
        assert bulk_admitted == 1
        st = lim.stats()
        assert st["vip"] == {"admitted": 16, "rate_limited": 4}
        assert st["bulk"] == {"admitted": 1, "rate_limited": 19}

    def test_limiter_lru_bounded(self):
        p = QosPolicy(rate_rps=1.0, max_tenants=4)
        clock = [0.0]
        lim = TenantRateLimiter(p, clock=lambda: clock[0])
        for i in range(32):
            lim.take(f"spoofed-{i}")
        assert len(lim._buckets) <= 4
        assert len(lim._stats) <= 4

    def test_limiter_stats_keep_hot_tenant_under_churn(self):
        """Stats eviction is true LRU like the buckets: a long-lived busy
        tenant's cumulative counters must survive a rotating-spoofed-id
        flood (a reset would run dynamo_tenant_*_total backwards)."""
        p = QosPolicy(rate_rps=1000.0, max_tenants=4)
        clock = [0.0]
        lim = TenantRateLimiter(p, clock=lambda: clock[0])
        for i in range(50):
            clock[0] += 1.0
            lim.take("hot")
            lim.take(f"spoof-{i}")
        assert lim.stats()["hot"]["admitted"] == 50


# -- fair queue + budget splitter --------------------------------------------


class TestFairQueue:
    def test_weighted_pick_prefers_starved(self):
        fq = FairQueue()
        fq.touch("a")
        fq.touch("b")
        fq.charge("a", 100, 1.0)
        fq.charge("b", 100, 4.0)  # same service, 4x weight → less vt
        assert fq.pick(["a", "b"]) == 1
        # a newcomer joins at the FLOOR (b's clock — no credit for the
        # past it slept through) and wins the tie on least total service
        assert fq.pick(["a", "b", "new"]) == 2

    def test_weighted_share_converges(self):
        """Serving always-backlogged tenants by pick() splits service by
        weight (the WFQ contract the engine scheduler relies on)."""
        fq = FairQueue()
        served = {"small": 0, "big": 0}
        weights = {"small": 1.0, "big": 4.0}
        for _ in range(500):
            t = ["small", "big"][fq.pick(["small", "big"])]
            served[t] += 1
            fq.charge(t, 10, weights[t])
        assert served["big"] / served["small"] == pytest.approx(4.0, rel=0.1)

    def test_forget_absent(self):
        fq = FairQueue()
        fq.charge("a", 5, 1.0)
        fq.charge("b", 5, 1.0)
        fq.forget_absent(["b"])
        assert set(fq.virtual_times()) == {"b"}

    def test_hard_bounded_under_rotating_ids(self):
        """A never-idle engine fed rotating spoofed tenant ids must not
        grow the fair-queue table (the limiter is LRU-bounded; this is
        the matching bound on the scheduler side)."""
        fq = FairQueue(max_tenants=8)
        for i in range(1000):
            fq.pick([f"spoof-{i}", "steady"])
            fq.charge("steady", 1, 1.0)
        assert len(fq.virtual_times()) <= 8
        assert "steady" in fq.virtual_times()  # floor entry survives


class TestSplitPrefillBudget:
    @pytest.mark.parametrize(
        "remaining,chunk,budget,expect",
        [
            ([100, 100], 32, 0, [32, 32]),  # unlimited → full chunks
            ([100, 100], 32, 40, [32, 8]),
            ([10, 100], 32, 40, [10, 30]),
            ([100], 32, 8, [8]),
            ([100, 100], 32, 1, [1, 0]),  # progress guarantee
            ([0, 50], 32, 16, [0, 16]),
            ([], 32, 16, []),
        ],
    )
    def test_table(self, remaining, chunk, budget, expect):
        assert split_prefill_budget(remaining, chunk, budget) == expect


# -- admission gate -----------------------------------------------------------


class TestAdmissionTenantGate:
    def _ctl(self):
        qos = QosPolicy(
            tenant_map={"vip": "premium", "bulk": "batch"},
            rate_rps=1.0, burst=1.0,
        )
        return AdmissionController(AdmissionPolicy(max_pending=100), qos=qos)

    def test_over_rate_tenant_shed_with_own_retry_after(self):
        ctl = self._ctl()
        assert ctl.try_admit(0, tenant="bulk") is None
        err = ctl.try_admit(0, tenant="bulk")
        assert isinstance(err, OverloadedError)
        assert err.tenant == "bulk"
        assert 0 < err.retry_after_ms <= 60_000
        assert "rate quota" in str(err)
        # tenant throttling has its own counter: it must NOT feed the
        # capacity-shed counter behind the overload_share SLO (a
        # correctly-throttled abuser would page a healthy fleet)
        assert ctl.rate_limited == 1 and ctl.shed == 0
        # a different tenant is untouched by the bulk tenant's shed
        assert ctl.try_admit(0, tenant="vip") is None
        stats = ctl.tenant_stats()
        assert stats["bulk"]["rate_limited"] == 1
        assert stats["vip"]["admitted"] == 1

    def test_anonymous_traffic_shares_default_bucket(self):
        ctl = self._ctl()
        assert ctl.try_admit(0, tenant=None) is None
        # the default tenant has the default class (standard, weight 4):
        # burst 4 → three more, then shed
        for _ in range(3):
            assert ctl.try_admit(0, tenant=None) is None
        err = ctl.try_admit(0, tenant=None)
        assert isinstance(err, OverloadedError)

    def test_global_shed_does_not_burn_tenant_quota(self):
        """A request the worker can't take anyway (global queue full)
        must not consume the tenant's token or inflate its admitted
        stat — a retry storm through an overloaded worker would
        otherwise exhaust an innocent tenant's quota."""
        qos = QosPolicy(tenant_map={"t": "batch"}, rate_rps=1.0, burst=1.0)
        ctl = AdmissionController(AdmissionPolicy(max_pending=1), qos=qos)
        err = ctl.try_admit(5, tenant="t")  # over the global bound
        assert isinstance(err, OverloadedError)
        assert err.tenant is None  # a GLOBAL shed, not a tenant shed
        assert ctl.tenant_stats() == {}  # bucket untouched
        # the tenant's single burst token is still available
        assert ctl.try_admit(0, tenant="t") is None

    def test_no_qos_knobs_builds_no_limiter(self, monkeypatch):
        _clear_tenant_env(monkeypatch)
        monkeypatch.setattr(
            qos_mod.TenantRateLimiter, "__init__",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("limiter built with QoS off")
            ),
        )
        ctl = AdmissionController(AdmissionPolicy(max_pending=4))
        assert ctl.tenant_limiter is None
        assert ctl.try_admit(0, tenant="whoever") is None
        assert ctl.tenant_stats() == {}


# -- allocator: tenant accounting + class-tiered eviction ---------------------


class TestAllocatorQos:
    def _alloc(self, blocks=16, bs=4):
        from dynamo_tpu.engine_jax.allocator import BlockAllocator

        return BlockAllocator(blocks, bs)

    def test_tenant_block_accounting(self):
        al = self._alloc()
        a = al.allocate_sequence(list(range(1, 9)), tenant="t1", level=1)
        assert al.tenant_blocks == {"t1": 2}
        assert al.grow(a, 13)
        assert al.tenant_blocks == {"t1": 4}
        b = al.allocate_sequence(list(range(100, 105)), tenant="t2")
        assert al.tenant_blocks["t2"] == 2
        al.free_sequence(a)
        assert "t1" not in al.tenant_blocks
        al.free_sequence(b)
        assert al.tenant_blocks == {}

    def test_single_tenant_path_touches_no_dicts(self):
        al = self._alloc()
        a = al.allocate_sequence(list(range(1, 9)))
        al.grow(a, 12)
        al.free_sequence(a)
        assert al.tenant_blocks == {}
        assert al._block_level == {}

    def test_unregister_drops_stale_class_tag(self):
        """A block whose content is replaced must not carry its old
        owner's class into the reuse pool (a stale high tag would
        shelter low-class content from eviction forever)."""
        al = self._alloc()
        a = al.allocate_sequence(list(range(1, 9)), tenant="vip", level=2)
        al.note_tokens_computed(a, list(range(1, 9)))
        bid = a.block_ids[0]
        assert al._block_level[bid] == 2
        al._unregister(bid)
        assert bid not in al._block_level

    def test_lowest_class_reclaimable_evicted_first(self):
        """Two sealed prefixes at levels 0 and 2: pool pressure evicts the
        level-0 (batch) blocks first even though the level-2 (premium)
        blocks are older in LRU terms."""
        al = self._alloc(blocks=8, bs=4)
        # premium seals first (older LRU position)
        hi = al.allocate_sequence(list(range(1, 10)), tenant="vip", level=2)
        al.note_tokens_computed(hi, list(range(1, 10)))
        al.free_sequence(hi)
        lo = al.allocate_sequence(list(range(100, 109)), tenant="bulk", level=0)
        al.note_tokens_computed(lo, list(range(100, 109)))
        al.free_sequence(lo)
        assert al.reclaimable_blocks == 4  # 2 sealed each
        removed: list = []

        class Sink:
            def blocks_stored(self, parent, blocks):
                pass

            def blocks_removed(self, hashes):
                removed.extend(hashes)

        al.set_sink(Sink())
        # force eviction of exactly two blocks
        c = al.allocate_sequence(list(range(200, 224)))  # needs 6 fresh
        assert c is not None
        # the premium prefix survives: re-allocating it still prefix-hits
        al.free_sequence(c)
        hi2 = al.allocate_sequence(list(range(1, 10)), tenant="vip", level=2)
        assert hi2.cached_tokens == 8
        lo2 = al.allocate_sequence(list(range(100, 109)), tenant="bulk")
        assert lo2.cached_tokens == 0  # batch-tier blocks were the victims


# -- RPC propagation ----------------------------------------------------------


class TestRpcTenantPropagation:
    def test_tenant_header_reaches_engine_context(self, run, monkeypatch):
        _clear_tenant_env(monkeypatch)
        from dynamo_tpu.runtime.annotated import Annotated
        from dynamo_tpu.runtime.engine import AsyncEngine, Context
        from dynamo_tpu.runtime.rpc import RpcClient, RpcServer

        seen: list = []

        class Capture(AsyncEngine):
            async def generate(self, request: Context):
                seen.append(request.context.tenant)
                yield Annotated.from_data({"ok": 1})

        async def go():
            server = RpcServer(host="127.0.0.1", port=0)
            server.register("t.c.e", Capture())
            await server.start()
            try:
                client = await RpcClient.connect(f"127.0.0.1:{server.port}")
                try:
                    ctx = Context({"p": 1})
                    ctx.context.tenant = "acme"
                    items = [
                        i async for i in client.generate(
                            "t.c.e", {"p": 1}, context=ctx
                        )
                    ]
                    assert not items[0].is_error
                    # and without a tenant, the context stays None
                    items = [i async for i in client.generate("t.c.e", {})]
                    assert not items[0].is_error
                finally:
                    await client.close()
            finally:
                await server.stop()

        run(go())
        assert seen == ["acme", None]

    def test_rate_shed_carries_tenant_and_retry_after(self, run):
        from dynamo_tpu.runtime.annotated import Annotated
        from dynamo_tpu.runtime.engine import AsyncEngine, Context
        from dynamo_tpu.runtime.rpc import RpcClient, RpcServer

        class Echo(AsyncEngine):
            async def generate(self, request: Context):
                yield Annotated.from_data({"ok": 1})

        # weight-1 class + burst 1 ⇒ exactly one request, then shed
        qos = QosPolicy(
            tenant_map={"flooder": "batch"}, rate_rps=0.001, burst=1.0
        )

        async def go():
            server = RpcServer(
                host="127.0.0.1", port=0,
                admission=AdmissionController(
                    AdmissionPolicy(max_pending=100), qos=qos
                ),
            )
            server.register("t.c.e", Echo())
            await server.start()
            try:
                client = await RpcClient.connect(f"127.0.0.1:{server.port}")
                try:
                    ctx = Context({})
                    ctx.context.tenant = "flooder"
                    items = [
                        i async for i in client.generate(
                            "t.c.e", {}, context=ctx
                        )
                    ]
                    assert not items[0].is_error
                    with pytest.raises(OverloadedError) as ei:
                        async for _ in client.generate(
                            "t.c.e", {}, context=ctx, raise_transport=True
                        ):
                            pass
                    assert ei.value.tenant == "flooder"
                    assert ei.value.retry_after_ms > 0
                finally:
                    await client.close()
            finally:
                await server.stop()

        run(go())


# -- HTTP edge ----------------------------------------------------------------


class TestHttpEdgeTenant:
    def _service(self, qos=None):
        from dynamo_tpu.llm.engines import EchoEngineFull
        from dynamo_tpu.llm.http.service import HttpService, ModelManager

        manager = ModelManager()
        engine = EchoEngineFull(delay_s=0.0)
        manager.add_chat_model("echo", engine)
        svc = HttpService(manager, host="127.0.0.1", port=0, qos=qos)
        return svc

    def _seen_tenants(self, svc):
        """Wrap the chat engine to capture ctx.context.tenant."""
        from dynamo_tpu.runtime.engine import AsyncEngine

        inner = svc.manager.chat_engine("echo")
        seen: list = []

        class Wrap(AsyncEngine):
            async def generate(self, request):
                seen.append(request.context.tenant)
                async for item in inner.generate(request):
                    yield item

        svc.manager.add_chat_model("echo", Wrap())
        return seen

    def _body(self):
        return {
            "model": "echo",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4,
        }

    def test_header_and_key_map_extraction(self, run, monkeypatch):
        import aiohttp

        _clear_tenant_env(monkeypatch)
        qos = QosPolicy(key_map={"sk-zed": "zedcorp"})
        svc = self._service(qos=qos)
        seen = self._seen_tenants(svc)

        async def go():
            port = await svc.start()
            base = f"http://127.0.0.1:{port}"
            try:
                async with aiohttp.ClientSession() as s:
                    async with s.post(
                        f"{base}/v1/chat/completions", json=self._body(),
                        headers={"x-tenant-id": "acme"},
                    ) as r:
                        assert r.status == 200
                    async with s.post(
                        f"{base}/v1/chat/completions", json=self._body(),
                        headers={"authorization": "Bearer sk-zed"},
                    ) as r:
                        assert r.status == 200
                    async with s.post(
                        f"{base}/v1/chat/completions", json=self._body(),
                    ) as r:
                        assert r.status == 200
            finally:
                await svc.stop()

        run(go())
        # QoS on: anonymous traffic becomes the shared default tenant
        assert seen == ["acme", "zedcorp", qos_mod.DEFAULT_TENANT]

    def test_no_knobs_header_still_rides_context(self, run, monkeypatch):
        import aiohttp

        _clear_tenant_env(monkeypatch)
        svc = self._service()
        assert svc.qos is None and svc.tenant_limiter is None
        seen = self._seen_tenants(svc)

        async def go():
            port = await svc.start()
            try:
                async with aiohttp.ClientSession() as s:
                    async with s.post(
                        f"http://127.0.0.1:{port}/v1/chat/completions",
                        json=self._body(),
                        headers={"x-tenant-id": "acme"},
                    ) as r:
                        assert r.status == 200
                    async with s.post(
                        f"http://127.0.0.1:{port}/v1/chat/completions",
                        json=self._body(),
                    ) as r:
                        assert r.status == 200
            finally:
                await svc.stop()

        run(go())
        assert seen == ["acme", None]

    def test_per_tenant_class_slo_rows(self, run, monkeypatch):
        """ISSUE 11 satellite (carried PR9 remainder): with QoS on, edge
        TTFT/ITL samples are ALSO recorded under the tenant's class label,
        so the SLO engine fans out per-class ttft_p95/itl_p95 rows onto
        /debug/slo — without disturbing the model-level objective."""
        import aiohttp

        from dynamo_tpu.runtime import telemetry

        _clear_tenant_env(monkeypatch)
        monkeypatch.delenv("DYN_TPU_SLO", raising=False)
        telemetry.configure()
        qos = QosPolicy(
            classes=OrderedDict([("standard", 1.0), ("premium", 8.0)]),
            tenant_map={"acme": "premium"},
        )
        svc = self._service(qos=qos)

        async def go():
            port = await svc.start()
            base = f"http://127.0.0.1:{port}"
            try:
                async with aiohttp.ClientSession() as s:
                    async with s.post(
                        f"{base}/v1/chat/completions",
                        json=dict(self._body(), stream=True),
                        headers={"x-tenant-id": "acme"},
                    ) as r:
                        assert r.status == 200
                        await r.text()
                    async with s.get(f"{base}/debug/slo") as r:
                        return await r.json()
            finally:
                await svc.stop()

        state = run(go())
        try:
            store = telemetry.store()
            # the class-labeled series exists alongside the model-level one
            label_sets = store.labels_of("ttft_ms")
            assert {"model": "echo"} in label_sets
            assert {"model": "echo", "tenant": "premium"} in label_sets
            rows = [
                s for s in state["slo"]
                if s["slo"] == "ttft_p95"
                and s["labels"].get("tenant") == "premium"
            ]
            assert rows, "per-tenant ttft_p95 row missing from /debug/slo"
        finally:
            telemetry.configure()

    def test_edge_rate_limit_answers_tenant_429(self, run, monkeypatch):
        import aiohttp

        _clear_tenant_env(monkeypatch)
        qos = QosPolicy(
            tenant_map={"flooder": "batch"}, rate_rps=0.001, burst=1.0
        )
        svc = self._service(qos=qos)

        async def go():
            port = await svc.start()
            base = f"http://127.0.0.1:{port}"
            try:
                async with aiohttp.ClientSession() as s:
                    async with s.post(
                        f"{base}/v1/chat/completions", json=self._body(),
                        headers={"x-tenant-id": "flooder"},
                    ) as r:
                        assert r.status == 200
                    async with s.post(
                        f"{base}/v1/chat/completions", json=self._body(),
                        headers={"x-tenant-id": "flooder"},
                    ) as r:
                        assert r.status == 429
                        assert int(r.headers["Retry-After"]) >= 1
                        body = await r.json()
                        assert body["error"]["type"] == "overloaded_error"
                        assert "flooder" in body["error"]["message"]
                    # an innocent tenant still gets through
                    async with s.post(
                        f"{base}/v1/chat/completions", json=self._body(),
                        headers={"x-tenant-id": "bystander"},
                    ) as r:
                        assert r.status == 200
            finally:
                await svc.stop()

        run(go())


# -- aggregated engine (real tiny JAX engine) ---------------------------------


@pytest.fixture(scope="module")
def tiny_parts():
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.llama import LLAMA_PRESETS, init_params

    cfg = dataclasses.replace(LLAMA_PRESETS["tiny"], dtype=jnp.float32)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


async def _collect(engine, prompt, max_tokens, tenant=None):
    from dynamo_tpu.llm.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context

    req = PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
    )
    ctx = Context(req)
    if tenant is not None:
        ctx.context.tenant = tenant
    toks = []
    async for item in engine.generate(ctx):
        if item.is_error:
            raise AssertionError(item.error_message())
        toks.extend((item.data or {}).get("token_ids", []))
    return toks


class TestChunkedPrefillBudget:
    """Tentpole (a): the prefill duty cycle in the aggregated engine."""

    SHORT = list(range(1, 10))
    LONG = list(range(20, 180))  # 160 tokens

    def _run_leg(self, tiny_parts, run, *, prefill_chunk, budget):
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        from dynamo_tpu.llm.protocols.common import (
            PreprocessedRequest,
            SamplingOptions,
            StopConditions,
        )
        from dynamo_tpu.runtime.engine import Context

        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(
                max_slots=2, kv_block_size=8, max_model_len=320,
                decode_steps=2, prefill_chunk=prefill_chunk,
                prefill_budget=budget,
            ),
            cache_dtype=jnp.float32,
        )

        async def go():
            req = PreprocessedRequest(
                token_ids=list(self.SHORT),
                stop_conditions=StopConditions(max_tokens=96, ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0),
            )
            short_toks: list = []
            agen = engine.generate(Context(req)).__aiter__()
            first = await agen.__anext__()
            assert not first.is_error
            short_toks.extend((first.data or {}).get("token_ids", []))
            # the short stream is provably decoding NOW: the long prompt
            # is admitted mid-decode, so its prefill must interleave
            long_task = asyncio.create_task(_collect(engine, self.LONG, 4))
            async for item in agen:
                if item.is_error:
                    raise AssertionError(item.error_message())
                short_toks.extend((item.data or {}).get("token_ids", []))
            return short_toks, await long_task

        try:
            short, long_ = run(go())
            snap = engine.metrics_snapshot()
        finally:
            engine.close()
        return short, long_, engine.prefill_interleave_max, snap

    def test_interleave_bounded_and_outputs_bitwise_equal(
        self, tiny_parts, run, monkeypatch
    ):
        _clear_tenant_env(monkeypatch)
        # budgeted leg: chunk 32, 8 tokens/dispatch average
        short_b, long_b, interleave_b, snap_b = self._run_leg(
            tiny_parts, run, prefill_chunk=32, budget=8
        )
        # unbudgeted control leg: one dispatch swallows the whole prompt
        short_c, long_c, interleave_c, snap_c = self._run_leg(
            tiny_parts, run, prefill_chunk=192, budget=0
        )
        # the long prefill really ran while the short stream decoded, and
        # pacing kept any single dispatch's prefill work to one chunk
        assert 0 < interleave_b <= 32
        # the bound is observable in the single-tenant budget-only mode
        # (no tenant knobs set in this leg)
        assert snap_b["prefill_interleave_max"] == interleave_b
        assert "prefill_interleave_max" not in snap_c  # budget off
        # control: the full 160-token prompt rode one dispatch in front of
        # the live decode lane — the ITL spike the budget exists to kill
        assert interleave_c >= 160
        # greedy outputs are bitwise identical across the two legs
        assert short_b == short_c
        assert long_b == long_c
        assert len(short_b) == 96 and len(long_b) == 4


class TestEngineTenantScheduling:
    """Tentpole (b) in the engine: WFQ admission + KV budgets."""

    def test_wfq_admits_starved_tenant_past_backlog(
        self, tiny_parts, run, monkeypatch
    ):
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv(
            "DYN_TPU_TENANT_CLASSES", "batch:1,standard:4,premium:16"
        )
        monkeypatch.setenv(
            "DYN_TPU_TENANT_MAP", "abuser=batch,victim=standard"
        )
        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(max_slots=1, kv_block_size=8, max_model_len=128),
            cache_dtype=jnp.float32,
        )
        assert engine._qos is not None and engine._fair is not None
        order: list = []

        async def one(tag, tenant, prompt):
            await _collect(engine, prompt, 24, tenant=tenant)
            order.append(tag)

        async def go():
            tasks = [
                asyncio.create_task(one("a1", "abuser", list(range(1, 9)))),
                asyncio.create_task(one("a2", "abuser", list(range(11, 19)))),
                asyncio.create_task(one("a3", "abuser", list(range(21, 29)))),
            ]
            await asyncio.sleep(0.05)  # abuser backlog queued first
            tasks.append(
                asyncio.create_task(one("v", "victim", list(range(31, 39))))
            )
            await asyncio.gather(*tasks)

        try:
            run(go())
        finally:
            engine.close()
        # the victim's lone request does NOT wait behind the abuser's
        # whole backlog (FIFO would finish it last)
        assert order[-1] != "v"
        assert order.index("v") < order.index("a3")

    def test_kv_budget_defers_over_share_tenant(
        self, tiny_parts, run, monkeypatch
    ):
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_KV_FRAC", "0.4")
        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(
                max_slots=2, kv_block_size=8, max_model_len=256,
                num_kv_blocks=30,  # budget = 12 blocks
            ),
            cache_dtype=jnp.float32,
        )
        assert engine._tenant_kv_budget == 12
        order: list = []

        async def one(tag, tenant, prompt, n):
            await _collect(engine, prompt, n, tenant=tenant)
            order.append(tag)

        async def go():
            # victim decoding first (16 tokens ≈ a few hundred ms on CPU)
            v = asyncio.create_task(
                one("v", "victim", list(range(1, 17)), 48)
            )
            await asyncio.sleep(0.3)
            # abuser prompt needs 13 blocks > budget 12 while the victim
            # is active → deferred (work-conserving: admitted after)
            a = asyncio.create_task(
                one("a", "abuser", list(range(100, 200)), 2)
            )
            await asyncio.gather(v, a)

        try:
            run(go())
        finally:
            engine.close()
        assert order == ["v", "a"]

    def test_slot_budget_defers_concurrency_hog(
        self, tiny_parts, run, monkeypatch
    ):
        """Satellite (carried ROADMAP micro-remainder): per-tenant decode
        SLOT budgets. On a 3-slot engine at slot_frac=0.34 (budget 1), an
        abuser holding its slot defers its next admission while the victim
        is active — a 2-token abuser stream submitted later still finishes
        AFTER the abuser's own 24-token stream (without the budget it
        would take the free slot and finish first)."""
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_SLOT_FRAC", "0.34")
        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(max_slots=3, kv_block_size=8, max_model_len=128),
            cache_dtype=jnp.float32,
        )
        assert engine._tenant_slot_budget == 1
        order: list = []

        async def one(tag, tenant, prompt, n):
            await _collect(engine, prompt, n, tenant=tenant)
            order.append(tag)

        async def go():
            v = asyncio.create_task(
                one("v", "victim", list(range(1, 17)), 48)
            )
            await asyncio.sleep(0.3)
            a1 = asyncio.create_task(
                one("a1", "abuser", list(range(30, 38)), 24)
            )
            await asyncio.sleep(0.15)
            a2 = asyncio.create_task(
                one("a2", "abuser", list(range(50, 58)), 2)
            )
            await asyncio.gather(v, a1, a2)

        try:
            run(go())
        finally:
            engine.close()
        assert order.index("a1") < order.index("a2"), (
            "over-budget tenant's later stream jumped the slot budget"
        )

    def test_slot_budget_work_conserving_alone(
        self, tiny_parts, run, monkeypatch
    ):
        """An uncontended tenant may fill every slot despite the budget —
        and two budget-capped tenants on an empty engine never deadlock
        (merely-pending tenants are not contention)."""
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_SLOT_FRAC", "0.34")
        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(max_slots=3, kv_block_size=8, max_model_len=128),
            cache_dtype=jnp.float32,
        )
        try:
            async def go():
                tasks = [
                    _collect(engine, list(range(10 * i + 1, 10 * i + 8)), 8,
                             tenant="solo")
                    for i in range(3)
                ]
                return await asyncio.wait_for(asyncio.gather(*tasks), 120)

            outs = run(go())
            assert all(len(t) == 8 for t in outs)
        finally:
            engine.close()

    def test_two_over_budget_tenants_both_complete(
        self, tiny_parts, run, monkeypatch
    ):
        """Deadlock regression: two tenants whose prompts each exceed the
        per-tenant KV budget arrive on an EMPTY engine. Contention is
        defined as another tenant actively holding resources — merely
        pending must not count, or each would defer the other forever."""
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_KV_FRAC", "0.3")
        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(
                max_slots=2, kv_block_size=8, max_model_len=256,
                num_kv_blocks=40,  # budget = 12 blocks
            ),
            cache_dtype=jnp.float32,
        )
        try:
            async def go():
                # both prompts need 13 blocks > the 12-block budget
                a = asyncio.create_task(
                    _collect(engine, list(range(1, 101)), 2, tenant="t1")
                )
                b = asyncio.create_task(
                    _collect(engine, list(range(200, 300)), 2, tenant="t2")
                )
                return await asyncio.wait_for(asyncio.gather(a, b), 120)

            ta, tb = run(go())
            assert len(ta) == 2 and len(tb) == 2
        finally:
            engine.close()

    def test_stale_prefill_debt_resets_between_episodes(
        self, tiny_parts, run, monkeypatch
    ):
        """Debt left by a finished prompt's last paced chunk must not
        tax a later prompt's TTFT: once no lane is prefilling, the
        duty-cycle state drops to zero."""
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        _clear_tenant_env(monkeypatch)
        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(
                max_slots=2, kv_block_size=8, max_model_len=64,
                prefill_budget=8,
            ),
            cache_dtype=jnp.float32,
        )
        try:
            engine._prefill_debt = 500.0  # stale debt from a past episode
            toks = run(_collect(engine, list(range(1, 10)), 8))
            assert len(toks) == 8
            assert engine._prefill_debt == 0.0
        finally:
            engine.close()

    def test_zero_overhead_when_qos_off(self, tiny_parts, run, monkeypatch):
        """No DYN_TPU_TENANT_* knobs ⇒ no FairQueue/limiter is ever
        constructed, the allocator's tenant dicts stay empty, and the
        snapshot carries no tenants key (the PR5/PR6 guard pattern)."""
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        _clear_tenant_env(monkeypatch)

        def boom(*a, **k):
            raise AssertionError("QoS object built with knobs unset")

        monkeypatch.setattr(qos_mod.FairQueue, "__init__", boom)
        monkeypatch.setattr(qos_mod.TenantRateLimiter, "__init__", boom)
        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(max_slots=2, kv_block_size=8, max_model_len=64),
            cache_dtype=jnp.float32,
        )
        try:
            assert engine._qos is None and engine._fair is None
            assert engine._prefill_budget == 0
            assert engine._tenant_kv_budget == 0
            assert engine._tenant_slot_budget == 0
            toks = run(_collect(engine, list(range(1, 10)), 16))
            assert len(toks) == 16
            snap = engine.metrics_snapshot()
        finally:
            engine.close()
        assert "tenants" not in snap
        assert engine.allocator.tenant_blocks == {}
        assert engine.allocator._block_level == {}

    def test_tenant_snapshot_when_qos_on(self, tiny_parts, run, monkeypatch):
        import jax.numpy as jnp

        from dynamo_tpu.engine_jax.engine import EngineConfig, JaxServingEngine

        _clear_tenant_env(monkeypatch)
        monkeypatch.setenv("DYN_TPU_TENANT_MAP", "acme=premium")
        cfg, params = tiny_parts
        engine = JaxServingEngine(
            cfg, params,
            EngineConfig(max_slots=2, kv_block_size=8, max_model_len=64),
            cache_dtype=jnp.float32,
        )
        try:
            async def sample_mid_flight():
                task = asyncio.create_task(
                    _collect(engine, list(range(30, 40)), 24, tenant="acme")
                )
                # poll until the snapshot catches the request holding its
                # slot/blocks (robust to fast CPUs and slow jit compiles)
                snap = None
                for _ in range(400):
                    await asyncio.sleep(0.01)
                    s = engine.metrics_snapshot()
                    if (s.get("tenants") or {}).get("acme", {}).get(
                        "kv_blocks", 0
                    ) >= 1:
                        snap = s
                        break
                    if task.done():
                        break
                await task
                return snap

            snap = run(sample_mid_flight())
        finally:
            engine.close()
        assert snap is not None, "never caught the request in flight"
        te = snap["tenants"]["acme"]
        assert te["class"] == "premium"
        assert te["active_slots"] + te["queue_depth"] >= 1
        assert te["kv_blocks"] >= 1
        assert snap["prefill_interleave_max"] >= 0


# -- noisy-neighbor chaos gate (virtual time, deterministic) ------------------


class TestNoisyNeighborChaos:
    def test_abusive_tenant_cannot_move_victim_itl(self):
        """THE acceptance gate: one abusive tenant offered ~10-20x its
        quota moves another tenant's ITL p95 by <10% with zero victim
        sheds — and the no-QoS control leg proves the contention is real
        (same workload, victim p95 blown up by orders of magnitude)."""
        from tools.qos_sim import run_scenario

        res = run_scenario()
        v_alone = res["victim_alone"]
        v_qos = res["victim_with_abuser_qos"]
        v_ctrl = res["victim_with_abuser_no_qos"]
        # zero victim failures: every offered victim request completed
        assert v_qos["shed"] == 0
        assert v_qos["completed"] == v_qos["offered"] == v_alone["offered"]
        # isolation: ≤ 10% ITL p95 movement vs the victim-alone baseline
        assert v_qos["itl_p95_ms"] <= 1.10 * v_alone["itl_p95_ms"], res
        # the control leg demonstrates the contention is real
        assert v_ctrl["itl_p95_ms"] >= 2.0 * v_alone["itl_p95_ms"], res
        # the abuser pays: most of its flood is rate-shed, the rest is
        # paced — but it still makes progress (work-conserving, no DoS)
        assert res["abuser_qos"]["shed"] > res["abuser_qos"]["completed"]
        assert res["abuser_qos"]["completed"] > 0

    def test_deterministic(self):
        from tools.qos_sim import run_noisy_neighbor

        a = run_noisy_neighbor()
        b = run_noisy_neighbor()
        assert {t: o.to_dict() for t, o in a.items()} == {
            t: o.to_dict() for t, o in b.items()
        }

    def test_max_gap_bounded_by_duty_cycle(self):
        """With QoS on, the victim's worst single gap is one paced chunk
        dispatch; the control leg's worst gap is the unpaced prefill."""
        from tools.qos_sim import SimConfig, run_noisy_neighbor

        cfg = SimConfig()
        qos = run_noisy_neighbor(qos_on=True, cfg=cfg)["victim"]
        ctrl = run_noisy_neighbor(qos_on=False, cfg=cfg)["victim"]
        chunk_cost = (
            cfg.step_base_ms
            + cfg.prefill_chunk * cfg.prefill_ms_per_token
            + cfg.slots * cfg.decode_ms_per_lane
        )
        assert qos.itl_max_ms <= chunk_cost
        assert ctrl.itl_max_ms > chunk_cost


# -- telemetry: rollup, gauges, mock worker, llmctl ---------------------------


class TestTenantTelemetry:
    def _metrics(self, tenants):
        from dynamo_tpu.kv_router.protocols import ForwardPassMetrics

        return ForwardPassMetrics(
            request_total_slots=8, kv_total_blocks=100, model="m1",
            tenants=tenants,
        )

    def test_rollup_sums_tenants_across_workers(self):
        from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry

        ct = ClusterTelemetry("tq", clock=lambda: 100.0)
        ct.ingest("w0", self._metrics({
            "acme": {"class": "premium", "active_slots": 2, "queue_depth": 1,
                     "kv_blocks": 10, "admitted": 50, "rate_limited": 0},
        }))
        ct.ingest("w1", self._metrics({
            "acme": {"class": "premium", "active_slots": 1, "queue_depth": 0,
                     "kv_blocks": 5, "admitted": 30, "rate_limited": 10},
            "crawler": {"class": "batch", "active_slots": 0, "queue_depth": 0,
                        "kv_blocks": 0, "admitted": 0, "rate_limited": 40},
        }))
        roll = ct.rollup()
        te = roll["models"]["m1"]["tenants"]
        assert te["acme"]["active_slots"] == 3
        assert te["acme"]["kv_blocks"] == 15
        assert te["acme"]["admitted_total"] == 80
        assert te["acme"]["rate_limited_total"] == 10
        # first sight = no window yet: the cumulative share stands in
        assert te["acme"]["shed_share"] == pytest.approx(10 / 90, abs=1e-3)
        # the fully-throttled crawler reads as sustained-100%
        assert te["crawler"]["shed_share"] == 1.0
        assert te["crawler"]["class"] == "batch"

    def test_shed_share_is_windowed_not_cumulative(self, monkeypatch):
        """ISSUE 11 satellite (carried PR9 remainder): a tenant throttled an
        hour ago but clean NOW must read shed_share 0 — `llmctl tenant
        status` exit-2 reflects *current* throttling. The lifetime average
        stays available as shed_share_cumulative."""
        from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry
        from dynamo_tpu.runtime.telemetry import TelemetryPolicy

        t = [100.0]
        pol = TelemetryPolicy(fast_window=60.0, mid_window=60.0,
                              slow_window=60.0)
        ct = ClusterTelemetry("tq", policy=pol, clock=lambda: t[0],
                              expiry=1e9)

        def ingest(admitted, limited):
            ct.ingest("w0", self._metrics({
                "crawler": {"class": "batch", "active_slots": 0,
                            "queue_depth": 0, "kv_blocks": 0,
                            "admitted": admitted, "rate_limited": limited},
            }))

        ingest(0, 100)          # baseline
        t[0] += 5.0
        ingest(0, 200)          # +100 sheds inside the window: throttling NOW
        te = ct.rollup()["models"]["m1"]["tenants"]["crawler"]
        assert te["shed_share"] == 1.0
        assert te["shed_share_cumulative"] == 1.0
        assert te["shed_share_window_s"] == 60.0

        # an hour later the tenant is clean: offered traffic all admitted
        t[0] += 3600.0
        ingest(50, 200)         # +50 admitted, zero new sheds
        te = ct.rollup()["models"]["m1"]["tenants"]["crawler"]
        assert te["shed_share"] == 0.0, "history must not read as current"
        # cumulative keeps the lifetime story
        assert te["shed_share_cumulative"] == pytest.approx(200 / 250)

        # ...and a QUIET tenant (no offered traffic at all in the window)
        # is also not currently throttled
        t[0] += 3600.0
        ingest(50, 200)         # zero deltas
        te = ct.rollup()["models"]["m1"]["tenants"]["crawler"]
        assert te["shed_share"] == 0.0

    def test_windowed_shed_share_drives_tenant_status_exit(self):
        """The llmctl exit-2 predicate over the rollup rows: a historically-
        abused-but-now-clean tenant no longer trips it."""
        from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry
        from dynamo_tpu.runtime.telemetry import TelemetryPolicy

        t = [0.0]
        pol = TelemetryPolicy(fast_window=60.0, mid_window=60.0,
                              slow_window=60.0)
        ct = ClusterTelemetry("tq", policy=pol, clock=lambda: t[0],
                              expiry=1e9)
        m = {"crawler": {"class": "batch", "active_slots": 0,
                         "queue_depth": 0, "kv_blocks": 0,
                         "admitted": 0, "rate_limited": 500}}
        ct.ingest("w0", self._metrics(m))
        t[0] += 3600.0
        ct.ingest("w0", self._metrics(m))  # zero deltas: quiet for an hour

        def throttled(te):
            # the same predicate cli/llmctl.py applies per row
            return (te.get("rate_limited_total", 0) > 0
                    and te.get("shed_share", 0.0) >= 0.999)

        te = ct.rollup()["models"]["m1"]["tenants"]["crawler"]
        assert not throttled(te), "stale history must not page the operator"

    def test_tenant_gauges_render_and_parse(self):
        from dynamo_tpu.components.telemetry_aggregator import ClusterTelemetry

        from .promtext import parse_prometheus_text

        ct = ClusterTelemetry("tq", clock=lambda: 100.0)
        ct.ingest("w0", self._metrics({
            'we"ird\\ten{ant}': {"class": "standard", "active_slots": 1,
                                 "queue_depth": 2, "kv_blocks": 3,
                                 "admitted": 4, "rate_limited": 1},
        }))
        text = ct.render_prometheus()
        metrics = parse_prometheus_text(text)  # grammar + escaping valid
        assert "dynamo_tenant_active_slots" in metrics
        assert "dynamo_tenant_shed_share" in metrics
        # single-tenant fleets emit no tenant lines at all
        ct2 = ClusterTelemetry("tq", clock=lambda: 100.0)
        ct2.ingest("w0", self._metrics(None))
        assert "dynamo_tenant_" not in ct2.render_prometheus()

    def test_mock_worker_tenants(self):
        from dynamo_tpu.components.mock_worker import (
            MockWorkerStats,
            parse_tenant_shares,
        )

        assert parse_tenant_shares("acme:6,bigco:2,crawler:0") == {
            "acme": 6, "bigco": 2, "crawler": 0,
        }
        assert parse_tenant_shares("bare") == {"bare": 1}
        assert parse_tenant_shares("") is None
        # malformed shares are skipped, as documented — never coerced to
        # a share that emits traffic the drill didn't ask for
        assert parse_tenant_shares("a:6,b:abc") == {"a": 6}
        stats = MockWorkerStats(
            seed=1, tenants={"acme": 6, "crawler": 0}
        )
        for _ in range(5):
            stats.tick(requests=8)
        m = stats.metrics("m1")
        assert m.tenants["acme"]["admitted"] == 30
        assert m.tenants["acme"]["rate_limited"] == 0
        assert m.tenants["crawler"]["admitted"] == 0
        assert m.tenants["crawler"]["rate_limited"] > 0

    def test_llmctl_tenant_status_exit_codes(self, run, capsys):
        """End to end: mock tenant metrics → aggregator → statestore
        discovery → `llmctl tenant status` renders rows, exits 2 only
        while some tenant is throttled at sustained 100%."""
        from dynamo_tpu.components.mock_worker import MockWorkerStats
        from dynamo_tpu.components.telemetry_aggregator import (
            run_telemetry_aggregator,
        )
        from dynamo_tpu.cli.llmctl import amain
        from dynamo_tpu.runtime import telemetry
        from dynamo_tpu.runtime.bus import MessageBusServer
        from dynamo_tpu.runtime.distributed import (
            KV_METRICS_SUBJECT,
            DistributedRuntime,
        )
        from dynamo_tpu.runtime.statestore import StateStoreServer

        async def go():
            ss = StateStoreServer(port=0)
            bus = MessageBusServer(port=0)
            await ss.start()
            await bus.start()
            drt = await DistributedRuntime.create(ss.url, bus.url)
            pub = await DistributedRuntime.create(ss.url, bus.url)
            ns = pub.namespace("dynamo")
            ready = asyncio.Event()
            agg_task = asyncio.create_task(run_telemetry_aggregator(
                drt, "dynamo", port=0, host="127.0.0.1", ready=ready,
            ))
            await asyncio.wait_for(ready.wait(), 10)
            try:
                healthy = MockWorkerStats(seed=1, tenants={"acme": 4})
                healthy.tick(requests=4)
                await ns.publish(KV_METRICS_SUBJECT, {
                    "worker_id": "w0",
                    "metrics": healthy.metrics("m1").to_dict(),
                })
                await asyncio.sleep(0.2)
                rc = await amain([
                    "--statestore", ss.url, "tenant", "status",
                    "dyn://dynamo.telemetry.status",
                ])
                out = capsys.readouterr().out
                assert rc == 0
                assert "acme" in out and "shed_share=0.000" in out

                throttled = MockWorkerStats(
                    seed=2, tenants={"acme": 4, "crawler": 0}
                )
                throttled.tick(requests=4)
                await ns.publish(KV_METRICS_SUBJECT, {
                    "worker_id": "w0",
                    "metrics": throttled.metrics("m1").to_dict(),
                })
                await asyncio.sleep(0.2)
                rc = await amain([
                    "--statestore", ss.url, "tenant", "status",
                    "dyn://dynamo.telemetry.status",
                ])
                out = capsys.readouterr().out
                assert rc == 2
                assert "THROTTLED" in out and "crawler" in out
            finally:
                agg_task.cancel()
                try:
                    await agg_task
                except (asyncio.CancelledError, Exception):
                    pass
                await drt.shutdown()
                await pub.shutdown()
                await bus.stop()
                await ss.stop()

        run(go())
