"""Serving gateway: bucketed packed prefill, AOT warmup, donated decode,
async emit — all bit-identical to the plain continuous batcher."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.quant import QuantConfig
from repro.models.common import materialize
from repro.models.transformer import lm_build
from repro.serve import (ContinuousBatcher, Request, ServingGateway,
                         bucket_for, greedy_generate, prefill_buckets,
                         supports_bucketed_prefill)
from repro.serve.engine import prepare_params


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke("smollm-135m")
    params = materialize(lm_build(cfg), jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def prog_model(request):
    """The L2R smoke model at float32 compute and at bf16, the published
    config's compute dtype (the gateway keeps its f32 cache)."""
    cfg = dataclasses.replace(get_smoke("smollm-135m"), l2r=QuantConfig(),
                              compute_dtype=request.param)
    params = prepare_params(cfg, materialize(lm_build(cfg),
                                             jax.random.PRNGKey(0)))
    return cfg, params


def _mixed_requests(cfg, lengths, max_new=6, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, (L,)).astype(np.int32),
                    max_new_tokens=max_new)
            for i, L in enumerate(lengths)]


# ------------------------------------------------------------- buckets
def test_prefill_buckets_shape():
    assert prefill_buckets(128) == (8, 16, 32, 64, 128)
    assert prefill_buckets(100) == (8, 16, 32, 64, 100)
    assert prefill_buckets(8) == (8,)
    assert prefill_buckets(5) == (5,)
    bk = prefill_buckets(64)
    assert bucket_for(1, bk) == 8 and bucket_for(8, bk) == 8
    assert bucket_for(9, bk) == 16 and bucket_for(64, bk) == 64
    with pytest.raises(ValueError):
        bucket_for(65, bk)


def test_supports_bucketed_prefill_gates_recurrent():
    cfg = get_smoke("smollm-135m")
    assert supports_bucketed_prefill(cfg)
    # a recurrent mixer would carry pad contamination in its state
    for arch in ("mamba2-2.7b", "rwkv7-3b"):
        try:
            rec = get_smoke(arch)
        except (KeyError, ValueError, AssertionError):
            continue
        assert not supports_bucketed_prefill(rec)


# --------------------------------------------------- gateway bit-parity
def test_gateway_matches_plain_batcher_mixed_buckets(model):
    """Mixed prompt lengths spanning every bucket, served through the
    gateway (packed prefill + AOT + donation + async emit), produce
    exactly the plain batcher's token streams."""
    cfg, params = model
    lengths = (3, 8, 5, 11, 17, 23, 9, 31)  # buckets 8, 16, 32
    ref = _mixed_requests(cfg, lengths)
    eng = ContinuousBatcher(cfg, params, n_slots=3, max_len=32)
    for r in ref:
        eng.submit(r)
    eng.run(max_steps=1000)

    served = _mixed_requests(cfg, lengths)
    gw = ServingGateway(cfg, params, n_slots=4, max_len=32,
                        prefill_group=3)
    gw.run(served)
    gw.close()
    for a, b in zip(ref, served):
        assert b.done
        assert a.output == b.output, (a.uid, a.output, b.output)


def test_gateway_matches_straightline_greedy(model):
    """Each gateway stream equals an isolated greedy decode — batching
    composition (packed prefill rows, slot neighbors) moves no token."""
    cfg, params = model
    reqs = _mixed_requests(cfg, (8, 5, 11), max_new=6)
    refs = [np.asarray(greedy_generate(cfg, params,
                                       jnp.asarray(r.prompt[None]),
                                       steps=6, max_len=32))[0].tolist()
            for r in reqs]
    gw = ServingGateway(cfg, params, n_slots=2, max_len=32,
                        prefill_group=2)
    gw.run(reqs)
    gw.close()
    for r, ref in zip(reqs, refs):
        assert r.done and r.output[:6] == ref, (r.uid, r.output, ref)


def test_gateway_progressive_exit_level_parity(prog_model):
    """Progressive early-exit mode: tokens AND per-token MSDF exit
    levels match the plain batcher exactly (exit decisions ride the
    same streamed head regardless of batch composition)."""
    cfg, params = prog_model
    lengths = (4, 9, 6, 13)
    ref = _mixed_requests(cfg, lengths)
    eng = ContinuousBatcher(cfg, params, n_slots=2, max_len=32,
                            progressive=True, early_exit=True)
    for r in ref:
        eng.submit(r)
    eng.run(max_steps=1000)

    served = _mixed_requests(cfg, lengths)
    gw = ServingGateway(cfg, params, n_slots=3, max_len=32,
                        prefill_group=2, progressive=True, early_exit=True)
    gw.run(served)
    gw.close()
    for a, b in zip(ref, served):
        assert a.output == b.output, (a.uid, a.output, b.output)
        assert a.exit_levels == b.exit_levels
        assert a.prefill_exit_level == b.prefill_exit_level
    st = gw.stats()
    assert st["tokens"] == sum(len(r.output) for r in served)
    assert sum(st["exit_level_hist"]) == sum(
        len(r.exit_levels) for r in served)
    if cfg.compute_dtype == "bfloat16":
        # the AOT decode executable is compiled with SERVE_COMPILER_OPTIONS:
        # it keeps bf16 roundings that a plain jit of the same step drops
        args, _ = gw._decode_exe.args_info
        sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           args)
        plain = jax.jit(gw._decode_jit.__wrapped__).lower(*sds).compile()
        n_converts = lambda exe: exe.as_text().count(" convert(")
        assert n_converts(gw._decode_exe) > n_converts(plain)


# ------------------------------------------------------------ slot churn
def test_gateway_slot_churn_under_full_queue(model):
    """Many more requests than slots: every admission wave reuses freed
    slots (generation counters guard the EOS signals) and every request
    completes with its full budget."""
    cfg, params = model
    reqs = _mixed_requests(cfg, (6, 4, 7, 5, 9, 3, 8, 6, 5, 4, 7, 6),
                           max_new=4, seed=1)
    gw = ServingGateway(cfg, params, n_slots=2, max_len=32,
                        prefill_group=2)
    gw.run(reqs)
    gw.close()
    assert all(r.done for r in reqs)
    assert all(len(r.output) == 4 for r in reqs)
    st = gw.stats()
    assert st["completed"] == len(reqs)
    assert st["tokens"] == 4 * len(reqs)


def test_gateway_eos_retires_early(model):
    """EOS detection happens on the emit thread and frees the slot via
    the (slot, generation) signal: the stream stops AT the EOS token,
    exactly like the plain batcher, and lagged decodes are dropped."""
    cfg, params = model
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab, (8,)).astype(np.int32)
    ref = np.asarray(greedy_generate(cfg, params, jnp.asarray(prompt[None]),
                                     steps=3, max_len=32))[0]
    req = Request(uid=0, prompt=prompt, max_new_tokens=10,
                  eos_id=int(ref[1]))
    filler = _mixed_requests(cfg, (5, 6, 7), max_new=8, seed=3)
    gw = ServingGateway(cfg, params, n_slots=2, max_len=32,
                        prefill_group=2)
    gw.run([req] + filler)
    gw.close()
    assert req.done
    assert len(req.output) == 2 and req.output[-1] == int(ref[1])
    assert all(r.done and len(r.output) == 8 for r in filler)


# ------------------------------------------------------------ async emit
def test_gateway_async_emit_ordering_matches_sync(model):
    """The async emit thread appends tokens in sequence order per
    request: token streams equal the synchronous-emit gateway's (same
    machinery, inline) token for token."""
    cfg, params = model
    lengths = (5, 9, 4, 12, 7)
    sync = _mixed_requests(cfg, lengths)
    gw_s = ServingGateway(cfg, params, n_slots=2, max_len=32,
                          prefill_group=2, async_emit=False)
    gw_s.run(sync)
    gw_s.close()

    async_ = _mixed_requests(cfg, lengths)
    gw_a = ServingGateway(cfg, params, n_slots=2, max_len=32,
                          prefill_group=2, async_emit=True,
                          emit_queue_depth=2)
    gw_a.run(async_)
    gw_a.close()
    for a, b in zip(sync, async_):
        assert a.output == b.output, (a.uid, a.output, b.output)
        assert b.t_arrival is not None and b.t_first_token is not None
        assert b.t_complete is not None
        assert b.t_arrival <= b.t_first_token <= b.t_complete


def test_gateway_emit_thread_error_propagates(model):
    """A failure on the emit thread surfaces on the caller at flush
    time, not silently."""
    cfg, params = model
    gw = ServingGateway(cfg, params, n_slots=2, max_len=32,
                        prefill_group=2)
    gw._emit.put(("bogus-kind-causes-unpack-error",))
    with pytest.raises(BaseException):
        gw._emit.flush()
    gw.close()


# -------------------------------------------------------- AOT executables
def test_gateway_aot_warmup_covers_every_bucket(model):
    """Warmup compiles one executable per bucket plus the decode step;
    serving mixed lengths afterwards never touches the jit fallback."""
    cfg, params = model
    gw = ServingGateway(cfg, params, n_slots=2, max_len=32,
                        prefill_group=2, aot_warmup=True)
    assert set(gw._prefill_exe) == set(gw.buckets) == {8, 16, 32}
    assert gw._decode_exe is not None
    reqs = _mixed_requests(cfg, (3, 9, 20), max_new=3)
    gw.run(reqs)
    gw.close()
    assert all(r.done for r in reqs)
    # the fallback jit entry points were never traced
    assert gw._prefill_jit._cache_size() == 0
    assert gw._decode_jit._cache_size() == 0


def test_gateway_realtime_honors_arrival_stamps(model):
    """realtime=True delays admission to each request's t_arrival; the
    tokens still match the offline drain."""
    import time

    cfg, params = model
    lengths = (5, 7, 4)
    offline = _mixed_requests(cfg, lengths, max_new=3)
    gw1 = ServingGateway(cfg, params, n_slots=2, max_len=32,
                         prefill_group=2)
    gw1.run(offline)
    gw1.close()

    online = _mixed_requests(cfg, lengths, max_new=3)
    gw2 = ServingGateway(cfg, params, n_slots=2, max_len=32,
                         prefill_group=2)
    t0 = time.perf_counter()
    for i, r in enumerate(online):
        r.t_arrival = t0 + 0.02 * i
        gw2.submit(r)
    gw2.run(realtime=True)
    gw2.close()
    for a, b in zip(offline, online):
        assert a.output == b.output
        assert b.t_first_token >= b.t_arrival


# ------------------------------------------------- stamps and counters
@pytest.mark.parametrize("realtime", [False, True], ids=["offline", "realtime"])
def test_gateway_request_stamps_and_in_flight(model, realtime):
    """Every served request is stamped in order arrival <= admission <=
    first token <= completion; the requests of one packed prefill share
    one admission stamp (and one first-token stamp); the decode steps in
    flight at a dispatch never exceed what the emit queue can hold plus
    the item on the emit thread."""
    import time

    cfg, params = model
    depth = 2
    reqs = _mixed_requests(cfg, (5, 9, 4, 12, 7, 6, 3), max_new=5, seed=4)
    gw = ServingGateway(cfg, params, n_slots=3, max_len=32,
                        prefill_group=2, emit_queue_depth=depth)
    t0 = time.perf_counter()
    for i, r in enumerate(reqs):
        r.t_arrival = t0 + (0.01 * i if realtime else 0.0)
        gw.submit(r)
    gw.run(realtime=realtime)
    st = gw.stats()
    gw.close()
    for r in reqs:
        assert r.done
        assert r.t_arrival <= r.t_admit <= r.t_first_token <= r.t_complete
    by_admit: dict[float, set] = {}
    by_first: dict[float, set] = {}
    for r in reqs:
        by_admit.setdefault(r.t_admit, set()).add(r.uid)
        by_first.setdefault(r.t_first_token, set()).add(r.uid)
    assert len(by_admit) == st["prefills"]
    assert sorted(map(sorted, by_admit.values())) == \
        sorted(map(sorted, by_first.values()))
    assert all(len(g) <= 2 for g in by_admit.values())
    assert 0.0 <= st["decode_in_flight_mean"] <= depth + 1


def test_gateway_sync_emit_has_nothing_in_flight(model):
    """Inline emit lands every decode before the next dispatch."""
    cfg, params = model
    gw = ServingGateway(cfg, params, n_slots=2, max_len=32,
                        prefill_group=2, async_emit=False)
    gw.run(_mixed_requests(cfg, (5, 9, 4), max_new=4))
    st = gw.stats()
    gw.close()
    assert st["steps"] > 0 and st["decode_in_flight_mean"] == 0.0


def _check_stats_schema_fixed(cfg, params, progressive):
    gw = ServingGateway(cfg, params, n_slots=2, max_len=32,
                        prefill_group=2, progressive=progressive)
    before = set(gw.stats())
    assert "decode_in_flight_mean" in before
    gw.run(_mixed_requests(cfg, (5, 9, 4), max_new=4))
    after = gw.stats()
    gw.close()
    assert set(after) == before
    assert after["decode_in_flight_mean"] >= 0.0


def test_gateway_stats_schema_fixed(model):
    """stats() has the same keys from construction on, whatever was
    served."""
    _check_stats_schema_fixed(*model, progressive=False)


def test_gateway_progressive_stats_schema_fixed(prog_model):
    """The same with the progressive head's histograms."""
    _check_stats_schema_fixed(*prog_model, progressive=True)
