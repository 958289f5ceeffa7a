"""DecodeServe (paged-KV LLM decode tier) + the PR-8 API-redesign pins.

The decode engine must couple serving truth with allocator truth: prefill
bursts take the buddy/bypass path while steady-state decode appends stay
on the PAGE_UNIT frontend, eviction edges (context exactly at a page
boundary, tenants that die mid-prefill) never allocate a page no token can
use, mesh and vmap drivers agree bitwise, and any core's slice exports as
a ``pim-malloc-trace/v1`` tape that replays bitwise on hwsw.
Alongside it, the redesign's single-source-of-truth pins: `system.KINDS`
derives from `heap.REGISTRY` (a freshly registered kind auto-enrolls), and
PagePool eviction routes every free through the protocol so a double evict
is a deterministic sanitizer ``double_free`` tag, not a silent success.
"""
import numpy as np
import pytest

from repro.core import heap, sanitizer, system as sysm
from repro.kvcache.paged import PAGE_UNIT, PagePool
from repro.launch.serve_decode import (DECODE_PAGE, EVICT_EXTENT, EVICT_PAGE,
                                       PREFILL, DecodeServe, DecodeTraffic,
                                       serve_decode_session)
from repro.workloads.replay import replay

T = 4
HEAP = 1 << 20
BYPASS_MIN = 2048 + 1   # smallest size that skips the frontend classes


def _cfg(kind="sw"):
    return sysm.SystemConfig(kind=kind, heap_bytes=HEAP, num_threads=T)


def _tc(**kw):
    base = dict(seed=0, rounds=32, session_rate=1.0, num_tenants=4,
                prompt_choices=(24, 48, 120, 3000),
                decode_choices=(0, 8, 24, 120), max_context=144,
                queue_cap=8)
    base.update(kw)
    return DecodeTraffic(**base)


def _own_size(plan):
    """Per dispatched op: its size cell in the grid."""
    rounds = plan.rounds
    return plan.size.reshape(rounds, -1)[plan.disp_round, plan.slot]


# --------------------------------------------------------------------------
# report schema + accounting balance
# --------------------------------------------------------------------------
def test_report_schema_and_balance():
    rep = serve_decode_session(_cfg(), 2, 2, traffic=_tc())
    required = {
        "shape", "rounds", "placement", "seed", "page_size",
        "capacity_per_round", "sessions_offered", "sessions_dropped",
        "session_drop_rate", "sessions_prefilled", "sessions_completed",
        "sessions_evicted_overflow", "sessions_active_end", "backlog_end",
        "queue_depth_mean", "queue_depth_max", "drops_per_round",
        "decode_tokens_per_round", "prefill_tokens", "decode_tokens",
        "tokens_total", "tokens_per_sec", "decode_stalls",
        "ttft_p50_cyc", "ttft_p95_cyc", "ttft_p99_cyc",
        "alloc_p50_cyc", "alloc_p95_cyc", "alloc_p99_cyc",
        "prefill_allocs", "decode_page_allocs", "evict_frees",
        "ops", "ok_ops", "failed_allocs", "dropped_frees",
        "live_bytes", "conservation_residual", "hwm_bytes_per_rank",
        "hwm_bytes_max", "external_frag_mean", "modeled_wall_us",
        "us_per_op", "ops_per_sec", "accounting",
    }
    missing = required - set(rep)
    assert not missing, missing
    # the allocator side must be healthy and the serving side consistent
    assert rep["conservation_residual"] == 0
    assert rep["failed_allocs"] == 0 and rep["dropped_frees"] == 0
    assert rep["tokens_total"] == rep["prefill_tokens"] + rep["decode_tokens"]
    assert rep["tokens_per_sec"] > 0 and rep["ttft_p50_cyc"] > 0
    assert rep["alloc_p99_cyc"] >= rep["alloc_p50_cyc"] > 0
    # session conservation: every ended session ran through prefill, and
    # prefilled <= admitted = offered - dropped
    ended = (rep["sessions_completed"] + rep["sessions_evicted_overflow"])
    assert ended + rep["sessions_active_end"] == rep["sessions_prefilled"]
    assert rep["sessions_prefilled"] <= rep["sessions_offered"] - \
        rep["sessions_dropped"]
    assert len(rep["hwm_bytes_per_rank"]) == 2
    assert rep["hwm_bytes_max"] == max(rep["hwm_bytes_per_rank"])
    assert sum(rep["decode_tokens_per_round"]) == rep["decode_tokens"]


def test_plan_is_seed_deterministic():
    eng = DecodeServe(_cfg(), 2, 2, traffic=_tc(seed=11))
    a, b = eng.plan(), eng.plan()
    for f in ("op", "size", "ptr_ref", "disp_round", "opkind", "session"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert a.offered == b.offered and a.tenant_home == b.tenant_home


# --------------------------------------------------------------------------
# prefill bursts vs steady-state decode: op sizes AND backend paths differ
# --------------------------------------------------------------------------
def test_prefill_burst_vs_steady_state_paths():
    """Prefills malloc the whole prompt extent in one burst (long prompts
    through the buddy bypass), decode appends are single PAGE_UNIT pages
    that must stay on the thread-cache frontend (path hit/refill, never
    bypass)."""
    eng = DecodeServe(_cfg(), 2, 2, traffic=_tc())
    plan = eng.plan()
    _, resps = eng.run(plan)
    own_size = _own_size(plan)
    rounds = plan.rounds
    path = np.asarray(resps.path).reshape(rounds, -1)[plan.disp_round,
                                                      plan.slot]
    pre, dec = plan.opkind == PREFILL, plan.opkind == DECODE_PAGE
    assert pre.any() and dec.any()
    # prefill extent = ceil(prompt/page_size) pages in ONE op
    prompts = plan.s_prompt[plan.session[pre]]
    pages = -(-prompts // plan.page_size)
    np.testing.assert_array_equal(own_size[pre], pages * PAGE_UNIT)
    assert (own_size[pre] > PAGE_UNIT).all()          # bursts, not pages
    assert (own_size[pre] >= BYPASS_MIN).any()        # long prompts bypass
    assert (path[pre][own_size[pre] >= BYPASS_MIN] == 2).all()
    # steady state: every decode append is exactly one frontend page
    assert (own_size[dec] == PAGE_UNIT).all()
    assert np.isin(path[dec], (0, 1)).all()           # hit/refill only


def test_eviction_frees_everything_the_session_allocated():
    """For every ended session the planner schedules exactly its decode
    pages + its one extent as protocol frees (closed-loop: nothing is
    reclaimed host-side)."""
    eng = DecodeServe(_cfg(), 2, 2, traffic=_tc(rounds=48))
    plan = eng.plan()
    ended = np.flatnonzero(plan.s_end_round >= 0)
    for s in ended:
        mine = plan.session == s
        n_pages = int((mine & (plan.opkind == DECODE_PAGE)).sum())
        # frees enqueued at end may still be draining in the last rounds;
        # every *dispatched* free belongs to something this session alloced
        n_free_pages = int((mine & (plan.opkind == EVICT_PAGE)).sum())
        n_free_ext = int((mine & (plan.opkind == EVICT_EXTENT)).sum())
        assert n_free_pages <= n_pages and n_free_ext <= 1
        if plan.s_end_round[s] <= plan.rounds - 3:    # had time to drain
            assert n_free_pages == n_pages and n_free_ext == 1, s
    assert (plan.opkind >= EVICT_PAGE).sum() > 0


# --------------------------------------------------------------------------
# eviction edges
# --------------------------------------------------------------------------
def test_context_exactly_at_page_boundary_completes_without_extra_page():
    """prompt 32 + decode 16 = 48 = max_context: the session fills its
    last page exactly and completes — no overflow, and no page is ever
    allocated for the boundary position it can never write."""
    tc = _tc(prompt_choices=(32,), decode_choices=(16,), max_context=48,
             session_rate=0.5, rounds=40)
    eng = DecodeServe(_cfg(), 2, 2, traffic=tc)
    plan = eng.plan()
    done = plan.s_end_round >= 0
    assert done.any()
    assert not plan.s_overflow[done].any()
    np.testing.assert_array_equal(plan.s_tokens[done], 16)
    for s in np.flatnonzero(done):
        mine = plan.session == s
        # tokens 32..47 live in ONE decode page (the 48-boundary page is
        # never allocated)
        assert int((mine & (plan.opkind == DECODE_PAGE)).sum()) == 1, s


def test_overflow_evicts_at_boundary_without_allocating_dead_page():
    """decode budget 17 > the 16 tokens max_context leaves room for: the
    session is evicted on overflow at pos==max_context with exactly one
    decode page — the page for the un-writable position is never
    allocated."""
    tc = _tc(prompt_choices=(32,), decode_choices=(17,), max_context=48,
             session_rate=0.5, rounds=40)
    plan = DecodeServe(_cfg(), 2, 2, traffic=tc).plan()
    done = plan.s_end_round >= 0
    assert done.any() and plan.s_overflow[done].all()
    np.testing.assert_array_equal(plan.s_tokens[done], 16)
    for s in np.flatnonzero(done):
        mine = plan.session == s
        assert int((mine & (plan.opkind == DECODE_PAGE)).sum()) == 1, s


def test_tenant_dies_mid_prefill_frees_extent_only():
    """A prompt longer than max_context overflows during prefill: zero
    decode tokens, zero decode pages, and eviction frees exactly the
    prefill extent."""
    tc = _tc(prompt_choices=(3000,), decode_choices=(120,), max_context=64,
             session_rate=0.5, rounds=40)
    eng = DecodeServe(_cfg(), 2, 2, traffic=tc)
    plan, rep = eng.serve()
    done = plan.s_end_round >= 0
    assert done.any() and plan.s_overflow[done].all()
    assert (plan.s_tokens == 0).all()
    assert (plan.opkind != DECODE_PAGE).all()
    assert rep["decode_tokens"] == 0 and rep["evict_frees"] > 0
    assert rep["conservation_residual"] == 0 and rep["dropped_frees"] == 0


def test_decode_zero_budget_dies_after_prefill():
    """decode budget 0: the tenant prefills, emits nothing, and is evicted
    cleanly (no overflow) — extent freed, no decode pages."""
    tc = _tc(prompt_choices=(48,), decode_choices=(0,), session_rate=0.5,
             rounds=32)
    plan = DecodeServe(_cfg(), 2, 2, traffic=tc).plan()
    done = plan.s_end_round >= 0
    assert done.any() and not plan.s_overflow[done].any()
    assert (plan.s_tokens == 0).all()
    assert (plan.opkind != DECODE_PAGE).all()
    assert (plan.opkind == EVICT_EXTENT).sum() >= done.sum() - 1


# --------------------------------------------------------------------------
# drivers + export
# --------------------------------------------------------------------------
def test_decode_mesh_and_vmap_paths_agree():
    """mesh=None (shard_map over the rank mesh) == mesh=False (pure vmap)
    on the same plan, response for response."""
    tc = _tc(rounds=16)
    a = DecodeServe(_cfg(), 2, 2, traffic=tc, mesh=False)
    b = DecodeServe(_cfg(), 2, 2, traffic=tc, mesh=None)
    plan = a.plan()
    _, ra = a.run(plan)
    _, rb = b.run(plan)
    for f in ("ptr", "ok", "path", "latency_cyc", "backend_cyc"):
        np.testing.assert_array_equal(np.asarray(getattr(ra, f)),
                                      np.asarray(getattr(rb, f)), err_msg=f)


@pytest.mark.parametrize("kind", ["hwsw"])
def test_decode_trace_export_replays_bitwise(kind):
    """Any core's slice of the decode session exports as a
    pim-malloc-trace/v1 tape that replays bitwise through the workloads
    engine."""
    eng = DecodeServe(_cfg(kind), 2, 2, traffic=_tc(rounds=20))
    plan = eng.plan()
    _, resps = eng.run(plan)
    checked = 0
    for rk in range(2):
        for ck in range(2):
            tr = eng.trace(plan, rk, ck)
            if tr.ops == 0:
                continue
            assert tr.meta["workload"] == "llm-decode-paged-kv"
            r2, _, _ = replay(tr, kind)
            for f in ("ptr", "ok", "path", "moved", "latency_cyc"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(resps, f))[:, rk, ck, :],
                    np.asarray(getattr(r2, f)), err_msg=f"{rk},{ck}:{f}")
            checked += 1
    assert checked >= 2


# --------------------------------------------------------------------------
# PR-8 satellite pins: KINDS single source of truth
# --------------------------------------------------------------------------
def test_kinds_derives_from_registry():
    assert tuple(sysm.KINDS) == tuple(heap.REGISTRY)
    assert set(sysm.KINDS) == set(heap.kinds())
    assert {"sw", "hwsw", "strawman", "sanitizer"} <= \
        set(sysm.KINDS)


def test_fresh_kind_auto_enrolls_in_kinds():
    """Registering a backend is the ONLY enrollment step: it must appear
    in system.KINDS and heap.kinds() without touching system.py."""
    assert "dummy_pr8" not in sysm.KINDS

    @heap.register("dummy_pr8")
    def _dummy_step(cfg, state, req):   # pragma: no cover - never stepped
        raise NotImplementedError

    try:
        assert "dummy_pr8" in sysm.KINDS
        assert "dummy_pr8" in heap.kinds()
        # and SystemConfig accepts it (validation reads the registry)
        sysm.SystemConfig(kind="dummy_pr8", heap_bytes=HEAP, num_threads=T)
    finally:
        del heap.REGISTRY["dummy_pr8"]
    assert "dummy_pr8" not in sysm.KINDS


def test_unknown_kind_rejected_with_registry_listing():
    with pytest.raises(AssertionError, match="registered"):
        sysm.SystemConfig(kind="nope", heap_bytes=HEAP, num_threads=T)


# --------------------------------------------------------------------------
# PR-8 satellite pins: PagePool eviction through the protocol
# --------------------------------------------------------------------------
def test_pagepool_evict_drains_all_pages_past_thread_width():
    """evict() chunks ANY number of decode pages into T-wide protocol
    frees (the pre-PR-8 recorder truncated at T and leaked the tail)."""
    pool = PagePool(n_pages=1 << 14, num_threads=T, kind="sw")
    ext = pool.alloc_pages(4)
    pages = []
    for _ in range(3):          # 3*T single pages > one T-wide batch
        ids, resp = pool.alloc_page_batch(np.ones(T, bool))
        assert bool(np.asarray(resp.ok).all())
        pages.extend(int(p) for p in np.asarray(ids))
    live0 = pool.client.telemetry()["live_bytes"]
    out = pool.evict(int(ext[0]), pages)
    assert out == {"freed_pages": 3 * T, "dropped_frees": 0}
    assert pool.client.telemetry()["live_bytes"] < live0
    assert pool.client.telemetry()["conservation_residual"] == 0


def test_pagepool_double_evict_is_deterministic_sanitizer_tag():
    """Evicting the same session twice must NOT be a silent success: the
    stale page ids reach the backend's dropped-free path and the sanitizer
    tags them as deterministic double frees."""
    pool = PagePool(n_pages=1 << 14, num_threads=T, kind="sanitizer")
    ext = pool.alloc_pages(4)
    ids, resp = pool.alloc_page_batch(np.ones(T, bool))
    assert bool(np.asarray(resp.ok).all())
    pages = [int(p) for p in np.asarray(ids)]

    first = pool.evict(int(ext[0]), pages)
    assert first["dropped_frees"] == 0
    second = pool.evict(int(ext[0]), pages)
    # every repeated free is dropped, deterministically — twice gives the
    # same verdict
    assert second["dropped_frees"] == second["freed_pages"] + 1  # + extent
    rep = sanitizer.report(pool.client.state)
    assert rep["double_free"] >= T + 1
    assert pool.client.stats["dropped_frees"] >= T + 1
    third = pool.evict(int(ext[0]), pages)
    assert third == second
