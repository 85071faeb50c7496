"""Serving: the continuous-batching engine must emit byte-identical greedy
tokens per request vs sequential reference decoding AND vs the legacy
run-to-completion engine — under heterogeneous prompt lengths, permuted
arrival order, mid-stream slot refill, paged or contiguous KV layout, and a
mesh-bearing Runtime — while compiling exactly once per (prefill-bucket,
decode, insert). Plus the hardened admission path (empty prompts, over-long
prompts, page-pool exhaustion) and the scheduler/page-allocator units."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.models import lm
from repro.nn.common import Ctx
from repro.serve.config import ServeConfig
from repro.serve.engine import Engine, Request
from repro.serve.legacy import RunToCompletionEngine
from repro.serve.scheduler import Scheduler
from repro.serve.serve_step import greedy_sample

CFG = ArchConfig(name="serve-test", family="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv=2, d_ff=128, vocab=256, q_chunk=32, kv_chunk=32)

_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = lm.init_params(jax.random.key(0), CFG)
    return _PARAMS


_REF_CACHE = {}


def _reference_decode(params, prompt, max_new, max_len):
    key = (tuple(int(t) for t in prompt), max_new, max_len)
    if key in _REF_CACHE:
        return _REF_CACHE[key]
    toks = jnp.asarray(prompt)[None]
    _, caches = lm.prefill(params, {"tokens": toks}, Ctx(), CFG, max_len)
    # next token from a full forward (prefill logits path == forward path)
    logits, _ = lm.forward(params, {"tokens": toks}, Ctx(), CFG)
    cur = greedy_sample(logits[:, -1:])
    out = []
    pos = toks.shape[1]
    for _ in range(max_new):
        out.append(int(cur[0, 0]))
        logits, caches = lm.decode_step(params, caches, cur, pos, Ctx(), CFG)
        cur = greedy_sample(logits)
        pos += 1
    _REF_CACHE[key] = out
    return out


def _mixed_requests(seed=0, lens=(11, 5, 23, 3, 17, 9, 30, 7),
                    news=(6, 3, 9, 2, 12, 4, 5, 8)):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(1, CFG.vocab, size=n).astype(np.int32),
                    max_new=m) for n, m in zip(lens, news)]


# ---------------------------------------------------------------------------
# model-stack plumbing (prefill/decode parity with forward)
# ---------------------------------------------------------------------------


def test_prefill_logits_match_forward():
    params = _params()
    toks = jax.random.randint(jax.random.key(1), (2, 17), 0, CFG.vocab)
    lg_fwd, _ = lm.forward(params, {"tokens": toks}, Ctx(), CFG)
    lg_pre, _ = lm.prefill(params, {"tokens": toks}, Ctx(), CFG, max_len=32)
    np.testing.assert_allclose(np.asarray(lg_pre), np.asarray(lg_fwd),
                               rtol=2e-4, atol=2e-4)


def test_multi_step_decode_matches_full_forward():
    """Decode 5 tokens step-by-step; logits must match teacher-forced forward."""
    params = _params()
    toks = jax.random.randint(jax.random.key(2), (2, 20), 0, CFG.vocab)
    full, _ = lm.forward(params, {"tokens": toks}, Ctx(), CFG)
    _, caches = lm.prefill(params, {"tokens": toks[:, :15]}, Ctx(), CFG, max_len=24)
    for i in range(15, 20):
        lg, caches = lm.decode_step(params, caches, toks[:, i:i + 1], i, Ctx(), CFG)
        np.testing.assert_allclose(np.asarray(lg[:, 0]), np.asarray(full[:, i]),
                                   rtol=3e-4, atol=3e-4)


def test_segment_masked_prefill_is_byte_identical_per_prompt():
    """Right-padded rows with segment ids produce EXACTLY the logits of the
    prompt alone: -1e30 masking makes pad contributions exp to exact 0.0, so
    the engines' bucketed prefill cannot perturb greedy decoding. "Alone" is
    the same prompt in the same [2, S] call with every other position
    (padding and the other row) replaced by unrelated tokens: same shapes,
    so any difference can only leak through the mask, never from the
    backend blocking a matmul differently for another row count."""
    params = _params()
    rng = np.random.default_rng(7)
    p1 = rng.integers(1, CFG.vocab, size=11).astype(np.int32)
    p2 = rng.integers(1, CFG.vocab, size=5).astype(np.int32)
    S = 16
    toks = np.zeros((2, S), np.int32)
    segs = np.zeros((2, S), np.int32)
    toks[0, :11], toks[1, :5] = p1, p2
    segs[0, :11], segs[1, :5] = 1, 1
    lg, _ = lm.prefill(params, {"tokens": jnp.asarray(toks),
                                "segments": jnp.asarray(segs)}, Ctx(), CFG, 32)
    for row, p in ((0, p1), (1, p2)):
        alone = rng.integers(1, CFG.vocab, size=(2, S)).astype(np.int32)
        alone[row, :len(p)] = p
        alone_segs = np.ones((2, S), np.int32)
        alone_segs[row] = segs[row]
        solo, _ = lm.prefill(params, {"tokens": jnp.asarray(alone),
                                      "segments": jnp.asarray(alone_segs)},
                             Ctx(), CFG, 32)
        np.testing.assert_array_equal(np.asarray(lg[row, :len(p)]),
                                      np.asarray(solo[row, :len(p)]))
        # and the same logits as the unpadded prompt, to rounding
        ref, _ = lm.forward(params, {"tokens": jnp.asarray(p)[None]}, Ctx(), CFG)
        np.testing.assert_allclose(np.asarray(lg[row, :len(p)]),
                                   np.asarray(ref[0]), rtol=1e-5, atol=1e-5)


def test_decode_step_vector_positions():
    """Per-slot position vectors: two rows decoding at different timesteps
    match their own scalar-pos references bitwise."""
    params = _params()
    rng = np.random.default_rng(8)
    p1 = rng.integers(1, CFG.vocab, size=9).astype(np.int32)
    p2 = rng.integers(1, CFG.vocab, size=4).astype(np.int32)
    want1 = _reference_decode(params, p1, 5, 32)
    want2 = _reference_decode(params, p2, 5, 32)
    toks = np.zeros((2, 9), np.int32)
    segs = np.zeros((2, 9), np.int32)
    toks[0, :9], toks[1, :4] = p1, p2
    segs[0, :9], segs[1, :4] = 1, 1
    lg, caches = lm.prefill(params, {"tokens": jnp.asarray(toks),
                                     "segments": jnp.asarray(segs)}, Ctx(), CFG, 32)
    cur = jnp.stack([greedy_sample(lg[0:1, 8:9])[0], greedy_sample(lg[1:2, 3:4])[0]])
    pos = jnp.asarray([9, 4], jnp.int32)
    outs = [[], []]
    for _ in range(5):
        for b in range(2):
            outs[b].append(int(cur[b, 0]))
        lg2, caches = lm.decode_step(params, caches, cur, pos, Ctx(), CFG)
        cur = greedy_sample(lg2)
        pos = pos + 1
    assert outs[0] == want1
    assert outs[1] == want2


# ---------------------------------------------------------------------------
# engine equivalence: continuous == legacy == sequential reference
# ---------------------------------------------------------------------------


def test_engine_matches_reference():
    params = _params()
    reqs = _mixed_requests()
    Engine(params, CFG, serve=ServeConfig(n_slots=4, max_len=64)).run(reqs)
    for r in reqs:
        assert r.out.tolist() == _reference_decode(params, r.prompt, r.max_new, 64)
        assert r.stop == "length"


def test_continuous_matches_legacy_under_permuted_arrival():
    """Byte-identical greedy tokens per request vs the run-to-completion
    baseline, for every arrival order — outputs are a property of the
    request, never of scheduling."""
    params = _params()
    for perm_seed in (0, 1):
        reqs_c = _mixed_requests()
        reqs_l = _mixed_requests()
        order = np.random.default_rng(perm_seed).permutation(len(reqs_c))
        reqs_c = [reqs_c[i] for i in order]
        reqs_l = [reqs_l[i] for i in order]
        Engine(params, CFG, serve=ServeConfig(n_slots=4, max_len=64)).run(reqs_c)
        RunToCompletionEngine(params, CFG, batch=4, max_len=64).run(reqs_l)
        for rc, rl in zip(reqs_c, reqs_l):
            assert rc.out.tolist() == rl.out.tolist()


def test_mid_stream_refill():
    """8 requests through 4 slots with wildly mixed max_new: short requests
    finish and their slots refill from the queue mid-decode; every output
    still matches the sequential reference, and the engine provably
    refilled (more prefill waves than one) without idling slots."""
    params = _params()
    reqs = _mixed_requests(news=(2, 20, 2, 20, 2, 20, 2, 3))
    eng = Engine(params, CFG, serve=ServeConfig(n_slots=4, max_len=64))
    eng.run(reqs)
    for r in reqs:
        assert r.out.tolist() == _reference_decode(params, r.prompt, r.max_new, 64)
    c = eng.counters
    assert c["batches"] >= 2  # refill happened mid-stream
    assert c["requests_done"] == len(reqs)
    # continuous batching's whole point: waste only the drain-out tail,
    # far below the legacy engine's run-to-completion + dead-lane waste
    leg = RunToCompletionEngine(params, CFG, batch=4, max_len=64)
    leg.run(_mixed_requests(news=(2, 20, 2, 20, 2, 20, 2, 3)))
    assert c["wasted_decode_steps"] < leg.counters["wasted_decode_steps"]


def test_paged_vs_contiguous_parity():
    """Paged pool + page-map decode == contiguous slot-major decode, bitwise."""
    params = _params()
    reqs_p = _mixed_requests(seed=3)
    reqs_c = _mixed_requests(seed=3)
    ep = Engine(params, CFG, serve=ServeConfig(n_slots=4, max_len=64, page_size=16))
    ec = Engine(params, CFG, serve=ServeConfig(n_slots=4, max_len=64, page_size=None))
    assert ep.layout.paged and not ec.layout.paged
    ep.run(reqs_p)
    ec.run(reqs_c)
    for rp, rc in zip(reqs_p, reqs_c):
        assert rp.out.tolist() == rc.out.tolist()


def test_packed_prefill_matches_unpacked():
    """Segment-masked packed prefill (several prompts in one row) changes
    call count but not one output token."""
    params = _params()
    reqs_pk = _mixed_requests(seed=5, lens=(3, 5, 4, 7, 6, 2), news=(4,) * 6)
    reqs_un = _mixed_requests(seed=5, lens=(3, 5, 4, 7, 6, 2), news=(4,) * 6)
    sv = ServeConfig(n_slots=3, max_len=64, page_size=16)
    ep = Engine(params, CFG, serve=sv)
    eu = Engine(params, CFG, serve=sv.replace(pack_prefill=False))
    ep.run(reqs_pk)
    eu.run(reqs_un)
    for a, b in zip(reqs_pk, reqs_un):
        assert a.out.tolist() == b.out.tolist()
    assert ep.counters["prefill_calls"] < eu.counters["prefill_calls"]


def test_eos_stops_early_and_is_recorded():
    params = _params()
    rng = np.random.default_rng(11)
    p = rng.integers(1, CFG.vocab, size=9).astype(np.int32)
    ref = _reference_decode(params, p, 10, 64)
    eos = ref[3]  # stop at the 4th generated token
    cut = ref.index(eos)  # first occurrence wins
    eng = Engine(params, CFG, serve=ServeConfig(n_slots=2, max_len=64))
    [req] = eng.run([Request(prompt=p, max_new=10, eos=int(eos))])
    assert req.out.tolist() == ref[:cut + 1]  # eos token included
    assert req.stop == "eos"
    assert eng.ring.records[-1]["stop"] == "eos"
    # engine-default eos via ServeConfig
    eng2 = Engine(params, CFG,
                  serve=ServeConfig(n_slots=2, max_len=64, eos=int(eos)))
    [req2] = eng2.run([Request(prompt=p, max_new=10)])
    assert req2.out.tolist() == ref[:cut + 1]


def test_mesh_runtime_equivalence():
    """The same engine code path under a mesh-bearing Runtime: continuous
    and legacy agree token-for-token under dp x tp sharding."""
    from repro.api.execution import ExecutionConfig
    from repro.api.runtime import Runtime
    from repro.launch.mesh import make_mesh

    if jax.device_count() < 8:
        pytest.skip("needs the conftest-forced 8 fake devices")
    params = _params()
    mesh = make_mesh((2, 4), ("data", "model"))
    rt = Runtime(execution=ExecutionConfig(mesh=mesh))
    reqs_c = _mixed_requests(seed=9, lens=(11, 5, 17, 8), news=(5, 8, 3, 6))
    reqs_l = _mixed_requests(seed=9, lens=(11, 5, 17, 8), news=(5, 8, 3, 6))
    rt.serve(params, CFG, serve=ServeConfig(n_slots=4, max_len=64)).run(reqs_c)
    RunToCompletionEngine(params, CFG, batch=4, max_len=64, runtime=rt).run(reqs_l)
    for rc, rl in zip(reqs_c, reqs_l):
        assert rc.out.tolist() == rl.out.tolist()


# ---------------------------------------------------------------------------
# compile-bucket contract: one XLA trace per (prefill bucket, decode, insert)
# ---------------------------------------------------------------------------


def test_one_compile_per_bucket_and_single_decode_trace():
    """Heterogeneous prompt lengths must NOT retrace: prompts bucket to
    powers of two (one prefill compile per bucket hit), decode and insert
    each compile exactly once — mirroring the BudgetSchedule
    one-compile-per-bucket tests via the engine's trace counters."""
    params = _params()
    reqs = _mixed_requests(lens=(3, 5, 9, 17, 30, 11, 23, 4),
                           news=(3, 4, 5, 3, 4, 5, 3, 4))
    eng = Engine(params, CFG, serve=ServeConfig(n_slots=4, max_len=64,
                                                page_size=16))
    eng.run(reqs)
    tc = eng.trace_counts
    assert tc["decode"] == 1, tc
    assert tc["insert"] == 1, tc
    prefills = {k: v for k, v in tc.items() if k.startswith("prefill[")}
    assert prefills and all(v == 1 for v in prefills.values()), tc
    buckets = ServeConfig(n_slots=4, max_len=64, page_size=16).buckets()
    assert all(int(k[len("prefill["):-1]) in buckets for k in prefills), tc
    # second run with fresh lengths: already-traced shapes NEVER retrace —
    # every label still sits at exactly one compile
    eng.run(_mixed_requests(seed=2, lens=(6, 10, 29, 13), news=(3, 3, 3, 3)))
    assert all(v == 1 for v in eng.trace_counts.values()), eng.trace_counts


def test_serve_config_buckets():
    sv = ServeConfig(n_slots=2, max_len=64, page_size=16)
    assert sv.buckets() == (16, 32, 64)
    assert sv.bucket_for(1) == 16 and sv.bucket_for(17) == 32
    assert sv.bucket_for(64) == 64
    with pytest.raises(ValueError):
        sv.bucket_for(65)
    with pytest.raises(ValueError, match="multiple of"):
        ServeConfig(max_len=50, page_size=16)
    assert ServeConfig(n_slots=2, max_len=64, page_size=16).pool_pages == 9


# ---------------------------------------------------------------------------
# scheduler + page allocator units
# ---------------------------------------------------------------------------


def test_scheduler_page_lifecycle():
    sv = ServeConfig(n_slots=2, max_len=64, page_size=16)
    sched = Scheduler(sv, paged=True)
    assert len(sched.free_pages) == sv.pool_pages - 1  # page 0 reserved
    r = Request(prompt=np.ones(20, np.int32), max_new=10)
    sched.submit([r], now=0.0)
    [taken] = sched.take_wave(pack=True, align=16)
    slot = sched.place(taken, first_tok=1, now=0.0)
    assert len(slot.pages) == 2  # ceil((20 + 10) / 16)
    assert (sched.page_map[slot.idx][:2] > 0).all()
    assert (sched.page_map[slot.idx][2:] == 0).all()  # tail -> trash page
    assert len(sched.free_pages) == sv.pool_pages - 3
    sched.finish(slot, "length", now=1.0)
    assert len(sched.free_pages) == sv.pool_pages - 1  # all released
    assert (sched.page_map[slot.idx] == 0).all()
    assert r.stop == "length" and r.t_done == 1.0


def test_scheduler_fifo_head_of_line_blocking():
    """A head request that doesn't fit the page free list blocks the queue
    (strict FIFO — no overtaking), and fits again after frees."""
    sv = ServeConfig(n_slots=2, max_len=64, page_size=16, n_pages=5)
    sched = Scheduler(sv, paged=True)
    big = Request(prompt=np.ones(30, np.int32), max_new=30)   # 4 pages
    small = Request(prompt=np.ones(4, np.int32), max_new=4)   # 1 page
    sched.submit([big, small], now=0.0)
    s1 = sched.place(sched.take_wave(pack=True, align=16)[0], 1, 0.0)
    assert sched.take_wave(pack=True, align=16) == []  # 0 free pages: blocked
    assert sched.pending() == 1
    sched.finish(s1, "length", 1.0)
    assert [r is small for r in sched.take_wave(pack=True, align=16)] == [True]


def test_engine_completes_under_page_pressure():
    """A pool with room for only ~one request at a time degrades throughput,
    never correctness: strict FIFO + worst-case reservation is deadlock-free."""
    params = _params()
    reqs = _mixed_requests(seed=4, lens=(20, 9, 14, 6), news=(8, 6, 4, 6))
    sv = ServeConfig(n_slots=4, max_len=64, page_size=16, n_pages=5)
    eng = Engine(params, CFG, serve=sv)
    eng.run(reqs)
    for r in reqs:
        assert r.out.tolist() == _reference_decode(params, r.prompt, r.max_new, 64)


# ---------------------------------------------------------------------------
# hardening: admission checks, truncation, wasted-step accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine_cls", [Engine, RunToCompletionEngine])
def test_engine_rejects_empty_prompt(engine_cls):
    eng = engine_cls(_params(), CFG, batch=2, max_len=32)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.run([Request(prompt=np.zeros(0, np.int32), max_new=4)])
    assert eng.counters["batches"] == 0  # rejected before any device work


@pytest.mark.parametrize("engine_cls", [Engine, RunToCompletionEngine])
def test_engine_rejects_unservable_max_new(engine_cls):
    eng = engine_cls(_params(), CFG, batch=2, max_len=16)
    p = np.ones(4, np.int32)
    with pytest.raises(ValueError, match="max_new"):
        eng.run([Request(prompt=p, max_new=16)])
    with pytest.raises(ValueError, match="max_new"):
        eng.run([Request(prompt=p, max_new=0)])


def test_overlong_prompt_left_truncated_and_recorded():
    params = _params()
    rng = np.random.default_rng(3)
    long = rng.integers(1, CFG.vocab, size=40).astype(np.int32)
    max_new = 4
    eng = Engine(params, CFG, batch=2, max_len=32)
    [req] = eng.run([Request(prompt=long, max_new=max_new)])
    # left-truncation: the engine served the most recent max_len - max_new
    # tokens; output equals the reference decode of that suffix
    keep = long[-(32 - max_new):]
    assert req.out.tolist() == _reference_decode(params, keep, max_new, 32)
    dropped = len(long) - len(keep)
    assert req.truncated == dropped
    assert eng.counters["truncated_tokens"] == dropped
    assert eng.ring.records[-1]["truncated_tokens"] == dropped


def test_wasted_steps_counted_for_empty_lanes():
    """Two live requests in a 4-slot engine with an empty queue: the two
    free lanes decode garbage every step and are counted, not hidden —
    and never per-slot-synced to the host (one [B] transfer per step)."""
    params = _params()
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, CFG.vocab, size=9).astype(np.int32)
               for _ in range(2)]
    eng = Engine(params, CFG, serve=ServeConfig(n_slots=4, max_len=32))
    reqs = eng.run([Request(prompt=p, max_new=4) for p in prompts])
    c = eng.counters
    assert c["decode_steps"] == 3  # first token comes from prefill
    assert c["wasted_decode_steps"] == 2 * c["decode_steps"]
    assert c["requests_done"] == 2
    for r, p in zip(reqs, prompts):
        assert r.out.tolist() == _reference_decode(params, p, 4, 32)


def test_telemetry_summary_fields():
    params = _params()
    eng = Engine(params, CFG, serve=ServeConfig(n_slots=2, max_len=32))
    eng.run(_mixed_requests(seed=6, lens=(5, 9, 7), news=(3, 4, 2)))
    t = eng.telemetry()
    assert t["layout"] == "paged"
    assert t["requests_done"] == 3
    assert t["decode_tok_per_s"] > 0 and t["prefill_tok_per_s"] > 0
    assert t["latency_p50_s"] is not None and t["latency_p99_s"] >= t["latency_p50_s"]
    assert t["ttft_p50_s"] is not None
    assert t["trace_counts"]["decode"] == 1
    # per-request ring records carry the latency stamps
    rec = eng.ring.records[-1]
    assert {"prompt_len", "new_tokens", "stop", "queue_s", "ttft_s",
            "latency_s"} <= set(rec)


def test_paged_cache_specs():
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import paged_cache_specs
    from repro.serve import kv_cache

    if jax.device_count() < 8:
        pytest.skip("needs the conftest-forced 8 fake devices")
    from jax.sharding import PartitionSpec as P

    def spec_leaves(tree):
        return jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, P))

    sv = ServeConfig(n_slots=4, max_len=64, page_size=16)
    pools = jax.eval_shape(lambda: kv_cache.init_pools(CFG, sv))
    mesh = make_mesh((2, 4), ("data", "model"))
    leaves = spec_leaves(paged_cache_specs(pools, mesh, sv.pool_pages))
    assert leaves  # pool_pages=9 doesn't divide dp=2 -> replicated pages
    assert all(s == P(None, None, None, None, None) for s in leaves)
    sv2 = sv.replace(n_pages=16)  # 16 pages / dp=2 -> pages shard over data
    leaves2 = spec_leaves(paged_cache_specs(
        jax.eval_shape(lambda: kv_cache.init_pools(CFG, sv2)), mesh, 16))
    assert all(s in (P(None, ("data",), None, None, None),
                     P(None, "data", None, None, None)) for s in leaves2)
