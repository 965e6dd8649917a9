"""Multi-LoRA serving engine: adapter store, quantize/dequantize tree
roundtrip, segment-batched generation, end-to-end train driver smoke."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import smoke_cfg
from repro.core import LoRAQuantConfig
from repro.launch.serve import random_trained_lora
from repro.models import build_model
from repro.serving.engine import (
    AdapterStore,
    MultiLoRAEngine,
    Request,
    dequantize_adapter,
    iter_lora_linears,
    quantize_adapter_tree,
)
from repro.serving.faults import RequestStatus, UnknownAdapter
from repro.serving.telemetry import Telemetry


@pytest.fixture(scope="module")
def tiny_model():
    cfg = smoke_cfg("llama3.2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_quantize_adapter_tree_roundtrip(tiny_model):
    cfg, model, params = tiny_model
    lora = random_trained_lora(params["lora"], jax.random.PRNGKey(1))
    qa = quantize_adapter_tree(lora, LoRAQuantConfig(rho=0.9, ste_steps=0))
    assert 1.0 < qa.avg_bits() < 2.5
    deq = dequantize_adapter(qa, lora)
    # structure and shapes preserved
    for (pa, la), (pb, lb) in zip(
            jax.tree_util.tree_flatten_with_path(lora)[0],
            jax.tree_util.tree_flatten_with_path(deq)[0]):
        assert la.shape == lb.shape and la.dtype == lb.dtype


def test_adapter_store_stats_and_lru(tiny_model):
    cfg, model, params = tiny_model
    store = AdapterStore(LoRAQuantConfig(rho=0.8, ste_steps=0),
                         fp_cache_bytes=1)   # force eviction
    for i in range(3):
        lora = random_trained_lora(params["lora"], jax.random.PRNGKey(i))
        store.register(f"u{i}", lora)
    stats = store.stats()
    assert stats["adapters"] == 3
    assert stats["quantized_mb"] < stats["fp16_equiv_mb"] / 5  # ≥5× smaller
    store.materialize("u0", params["lora"])
    store.materialize("u1", params["lora"])
    assert len(store._lru) == 1              # byte budget forces eviction


def test_engine_end_to_end(tiny_model):
    cfg, model, params = tiny_model
    store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    for i in range(2):
        store.register(f"u{i}", random_trained_lora(
            params["lora"], jax.random.PRNGKey(10 + i)))
    engine = MultiLoRAEngine(model, params, store, cache_capacity=64)
    rng = np.random.default_rng(0)
    for rid in range(4):
        engine.submit(Request(
            request_id=rid, adapter_id=f"u{rid % 2}",
            prompt=rng.integers(0, cfg.vocab, size=12).astype(np.int32),
            max_new_tokens=4))
    done = engine.run()
    assert len(done) == 4
    for r in done:
        assert r.output.shape == (4,)
        assert (0 <= r.output).all() and (r.output < cfg.vocab).all()


def test_quantized_vs_fp_adapter_outputs_close(tiny_model):
    """Serving with a LoRAQuant-compressed adapter should stay close to the
    fp adapter on logits (the paper's claim, reconstruction proxy)."""
    cfg, model, params = tiny_model
    lora = random_trained_lora(params["lora"], jax.random.PRNGKey(5),
                               scale=0.05)
    qa = quantize_adapter_tree(lora, LoRAQuantConfig(rho=0.95, bits_high=3,
                                                     refine="als"))
    deq = dequantize_adapter(qa, lora)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (1, 16)))
    lf, _ = model.forward({"base": params["base"], "lora": lora},
                          {"tokens": toks})
    lq, _ = model.forward({"base": params["base"], "lora": deq},
                          {"tokens": toks})
    l0, _ = model.forward(params, {"tokens": toks})  # zero-init lora = base
    # quantized adapter must be much closer to the fp adapter than to base
    d_q = float(jnp.linalg.norm(lq - lf))
    d_0 = float(jnp.linalg.norm(l0 - lf))
    assert d_q < 0.5 * d_0


# --------------------------------------------------------------------------
# heterogeneous packed serving (decode straight from packed codes)
# --------------------------------------------------------------------------

def _mk_requests(cfg, n, n_adapters, seed=7, prompt_lens=None, max_new=None):
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = prompt_lens[rid] if prompt_lens else 8
        reqs.append(Request(
            request_id=rid, adapter_id=f"u{rid % n_adapters}",
            prompt=rng.integers(0, cfg.vocab, size=plen).astype(np.int32),
            max_new_tokens=max_new[rid] if max_new else 4))
    return reqs


def _run_both_modes(model, params, store, reqs_fn):
    engine = MultiLoRAEngine(model, params, store, cache_capacity=64)
    for r in reqs_fn():
        engine.submit(r)
    packed = {r.request_id: r.output for r in engine.run(mode="packed")}
    # acceptance: packed decode allocates NO per-adapter fp LoRA trees
    assert len(store._lru) == 0 and store.fp_resident_bytes() == 0
    for r in reqs_fn():
        engine.submit(r)
    ref = {r.request_id: r.output for r in engine.run(mode="materialize")}
    assert store.fp_resident_bytes() > 0
    return packed, ref


def test_packed_heterogeneous_matches_reference(tiny_model):
    """One mixed-adapter batch from packed codes == the segment-loop fp
    reference, token for token: mixed prompt lengths, three adapters with
    different per-layer split indices h, and one request that finishes
    early (smaller max_new_tokens)."""
    cfg, model, params = tiny_model
    store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    for i in range(3):
        store.register(f"u{i}", random_trained_lora(
            params["lora"], jax.random.PRNGKey(40 + i), scale=0.05))
    hs = {q.h for qa in store.quantized.values()
          for qs in qa.entries.values() for q in qs}
    assert len(hs) > 1                       # genuinely heterogeneous splits

    packed, ref = _run_both_modes(
        model, params, store,
        lambda: _mk_requests(cfg, 4, 3, prompt_lens=[5, 8, 11, 8],
                             max_new=[4, 2, 4, 4]))
    assert packed.keys() == ref.keys()
    for rid in packed:
        np.testing.assert_array_equal(packed[rid], ref[rid])
    assert len(packed[1]) == 2               # early finisher kept its length


@pytest.mark.slow
def test_packed_3bit_adapter_parity(tiny_model):
    """The packed path must serve 3-bit (uint32-packed) adapters — the
    width the two-pass kernels cannot do — identically to the reference."""
    cfg, model, params = tiny_model
    store = AdapterStore(LoRAQuantConfig(rho=0.9, bits_high=3, ste_steps=0))
    for i in range(2):
        store.register(f"u{i}", random_trained_lora(
            params["lora"], jax.random.PRNGKey(50 + i), scale=0.05))
    packed, ref = _run_both_modes(
        model, params, store, lambda: _mk_requests(cfg, 3, 2, seed=11))
    for rid in packed:
        np.testing.assert_array_equal(packed[rid], ref[rid])


def test_register_invalidates_fp_lru(tiny_model):
    """Regression: re-registering an adapter_id must not keep serving the
    old fp tree out of the LRU."""
    cfg, model, params = tiny_model
    store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    t_old = random_trained_lora(params["lora"], jax.random.PRNGKey(60))
    t_new = random_trained_lora(params["lora"], jax.random.PRNGKey(61))
    store.register("u", t_old)
    stale = store.materialize("u", params["lora"])
    store.register("u", t_new)               # user re-uploads their adapter
    assert len(store._lru) == 0              # fp cache invalidated
    fresh = store.materialize("u", params["lora"])
    direct = dequantize_adapter(store.quantized["u"], params["lora"])
    got = jax.tree_util.tree_leaves(fresh)
    want = jax.tree_util.tree_leaves(direct)
    old = jax.tree_util.tree_leaves(stale)
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    assert not all(np.array_equal(g, o) for g, o in zip(got, old))


def test_register_many_bucketed_onboarding_equivalence(tiny_model):
    """Cross-adapter bucketed onboarding (one quantize_lora_stacks dispatch
    per leaf shape) must produce the same quantized adapters as registering
    each tree on its own."""
    cfg, model, params = tiny_model
    trees = {f"u{i}": random_trained_lora(params["lora"],
                                          jax.random.PRNGKey(70 + i))
             for i in range(3)}
    one_by_one = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    for k, v in trees.items():
        one_by_one.register(k, v)
    bucketed = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    bucketed.register_many(trees)
    assert set(bucketed.quantized) == set(one_by_one.quantized)
    for k in trees:
        qa, qb = one_by_one.quantized[k], bucketed.quantized[k]
        assert set(qa.entries) == set(qb.entries)
        for path in qa.entries:
            for x, y in zip(qa.entries[path], qb.entries[path]):
                assert (x.h, x.rank) == (y.h, y.rank)
                np.testing.assert_array_equal(np.asarray(x.a_high.codes),
                                              np.asarray(y.a_high.codes))
                np.testing.assert_array_equal(np.asarray(x.b_high.codes),
                                              np.asarray(y.b_high.codes))
                np.testing.assert_allclose(np.asarray(x.a_high.scale),
                                           np.asarray(y.a_high.scale),
                                           rtol=1e-6, atol=0)


# --------------------------------------------------------------------------
# continuous-batching scheduler semantics
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served_store(tiny_model):
    cfg, model, params = tiny_model
    store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    for i in range(2):
        store.register(f"u{i}", random_trained_lora(
            params["lora"], jax.random.PRNGKey(90 + i), scale=0.05))
    return store


@pytest.fixture(scope="module")
def cont_engine(tiny_model, served_store):
    """One continuous engine shared by the scheduler tests (max_rows=2 so
    4-request workloads must reuse freed slots)."""
    cfg, model, params = tiny_model
    return MultiLoRAEngine(model, params, served_store, cache_capacity=64,
                           max_rows=2)


def _sched_requests(cfg):
    return _mk_requests(cfg, 4, 2, seed=21, prompt_lens=[5, 8, 11, 8],
                        max_new=[6, 2, 6, 2])


def test_continuous_matches_static_packed(tiny_model, served_store,
                                          cont_engine):
    """Acceptance: with every request submitted up front, the continuous
    scheduler (here forced through slot reuse: 4 requests, 2 rows) is
    token-for-token the static one-batch packed run."""
    cfg, model, params = tiny_model
    for r in _sched_requests(cfg):
        cont_engine.submit(r)
    cont = {r.request_id: r.output for r in cont_engine.run()}
    assert served_store.fp_resident_bytes() == 0      # packed codes only

    static = MultiLoRAEngine(model, params, served_store, cache_capacity=64)
    for r in _sched_requests(cfg):
        static.submit(r)
    ref = {r.request_id: r.output for r in static.run(mode="packed")}
    assert cont.keys() == ref.keys()
    for rid in ref:
        np.testing.assert_array_equal(cont[rid], ref[rid])


def test_mid_decode_admission_matches_solo(tiny_model, cont_engine):
    """A request admitted while another is mid-decode must produce exactly
    the tokens of a solo run — per-row positions and pad masks keep every
    row independent."""
    cfg, model, params = tiny_model
    [r_bg, _, r_new, _] = _sched_requests(cfg)

    cont_engine.submit(dataclasses.replace(r_new))
    solo = cont_engine.run()[0].output                # solo reference

    cont_engine.submit(dataclasses.replace(r_bg))
    done = cont_engine.step() + cont_engine.step()    # r_bg is mid-decode
    assert cont_engine.active_rows == 1
    cont_engine.submit(dataclasses.replace(r_new))    # arrives mid-decode
    while cont_engine.pending or cont_engine.active_rows:
        done += cont_engine.step()
    got = {r.request_id: r.output for r in done}
    np.testing.assert_array_equal(got[r_new.request_id], solo)


def test_early_finish_frees_slot_for_pending(tiny_model, cont_engine):
    """Rows retiring at max_new_tokens free their slot immediately: 4
    requests drain through 2 rows, short ones finishing first."""
    cfg, model, params = tiny_model
    reqs = _sched_requests(cfg)
    for r in reqs:
        cont_engine.submit(r)
    order = []
    while cont_engine.pending or cont_engine.active_rows:
        order += [r.request_id for r in cont_engine.step()]
    assert sorted(order) == [0, 1, 2, 3]
    assert cont_engine.active_rows == 0               # all slots freed
    # the short request admitted first (id 1, max_new=2) must finish before
    # the long one admitted alongside it (id 0, max_new=6)
    assert order.index(1) < order.index(0)
    for r in reqs:
        assert r.output.shape == (r.max_new_tokens,)


def test_eos_retires_row_early(tiny_model, served_store, cont_engine):
    """eos_id retirement: output stops at (and includes) the first EOS, and
    the static packed path truncates identically."""
    cfg, model, params = tiny_model
    base_req = _sched_requests(cfg)[0]
    cont_engine.submit(dataclasses.replace(base_req))
    free = cont_engine.run()[0].output                # unconstrained tokens
    eos = int(free[1])
    first = int(np.nonzero(free == eos)[0][0])
    expect = free[: first + 1]

    cont_engine.submit(dataclasses.replace(base_req, eos_id=eos))
    got = cont_engine.run()[0].output
    np.testing.assert_array_equal(got, expect)

    static = MultiLoRAEngine(model, params, served_store, cache_capacity=64)
    static.submit(dataclasses.replace(base_req, eos_id=eos))
    np.testing.assert_array_equal(static.run(mode="packed")[0].output, expect)


def test_mid_decode_register_keeps_row_adapters(tiny_model, served_store,
                                                cont_engine):
    """Registering a new adapter mid-decode reorders/extends the store-wide
    packed stack; live rows must re-resolve their segment index against the
    new order instead of serving a neighbor's adapter."""
    cfg, model, params = tiny_model
    req = _sched_requests(cfg)[2]
    cont_engine.submit(dataclasses.replace(req))
    solo = cont_engine.run()[0].output

    cont_engine.submit(dataclasses.replace(req))
    done = cont_engine.step() + cont_engine.step()
    # "a_first" sorts before the u* ids, shifting every existing index
    served_store.register("a_first", random_trained_lora(
        params["lora"], jax.random.PRNGKey(99), scale=0.05))
    while cont_engine.pending or cont_engine.active_rows:
        done += cont_engine.step()
    np.testing.assert_array_equal(done[-1].output, solo)


def _free_outputs(cfg, model, params, store):
    """The scheduler requests' outputs with no EOS (solo-exact)."""
    eng = MultiLoRAEngine(model, params, store, cache_capacity=64,
                          max_rows=2)
    for r in _sched_requests(cfg):
        eng.submit(r)
    return {r.request_id: r.output for r in eng.run()}


def test_overlapped_decode_matches_static_with_eos_tail(tiny_model,
                                                        served_store):
    """Step n dispatches decode n before it reads back decode n−1's
    tokens. With one row hitting its EOS mid-decode (the decode already in
    flight for it is discarded) and short rows finishing beside long ones,
    the continuous run is token-for-token the static packed one."""
    cfg, model, params = tiny_model
    free = _free_outputs(cfg, model, params, served_store)
    eos = int(free[0][2])

    def reqs():
        rs = _sched_requests(cfg)
        rs[0] = dataclasses.replace(rs[0], eos_id=eos)
        return rs

    tel = Telemetry()
    eng = MultiLoRAEngine(model, params, served_store, cache_capacity=64,
                          max_rows=2, telemetry=tel)
    for r in reqs():
        eng.submit(r)
    cont = {r.request_id: r.output for r in eng.run()}
    static = MultiLoRAEngine(model, params, served_store, cache_capacity=64)
    for r in reqs():
        static.submit(r)
    ref = {r.request_id: r.output for r in static.run(mode="packed")}
    assert cont.keys() == ref.keys()
    for rid in ref:
        np.testing.assert_array_equal(cont[rid], ref[rid])
    assert cont[0].size <= 3 < 6                  # stopped at its EOS
    assert cont[0][-1] == eos
    assert tel.registry.value("serving_decode_discarded_tokens_total") == 1


def test_read_back_contract_two_tokens_then_one_per_step(tiny_model,
                                                         served_store):
    """What a caller that stamps tokens per ``step()`` relies on: a
    request's ``t_first`` becomes visible in the ``step()`` return that
    holds its first two tokens, and carries the clock of an earlier step's
    read-back; every later step adds one token per live request; a request
    is returned in the step that delivered its last token (budget or
    EOS). So one stamp for ``t_first`` and one per step return count its
    output exactly, arrivals mid-decode included."""
    cfg, model, params = tiny_model
    free = _free_outputs(cfg, model, params, served_store)
    # an EOS first met at token k >= 2: the request ends mid-decode, after
    # t_first has been published
    k = next(k for k in range(2, 6) if free[2][k] not in free[2][:k])
    reqs = _sched_requests(cfg)
    reqs[2] = dataclasses.replace(reqs[2], eos_id=int(free[2][k]))
    eng = MultiLoRAEngine(model, params, served_store, cache_capacity=64,
                          max_rows=2)
    for r in reqs[:2]:
        eng.submit(r)
    waiting, stamps, steps = list(reqs), {}, 0
    while eng.pending or eng.active_rows or steps < 2:
        t_call = eng.clock()
        fin = eng.step()
        steps += 1
        if steps == 2:
            for r in reqs[2:]:                    # arrive mid-decode
                eng.submit(r)
        for r in [r for r in waiting if r.t_first is not None]:
            waiting.remove(r)
            assert r.t_first < t_call             # stamped a step earlier
            row = next((x for x in eng._rows
                        if x is not None and x.req is r), None)
            assert row is None or len(row.emitted) == 2
            stamps[r.request_id] = 1
        for rid in stamps:
            if not reqs[rid].status.terminal or reqs[rid] in fin:
                stamps[rid] += 1
        for r in fin:
            assert r.status is RequestStatus.DONE
            assert len(r.output) == stamps[r.request_id], r.request_id
    assert not waiting and sorted(stamps) == [0, 1, 2, 3]
    assert reqs[2].output.size == k + 1


def test_overlapped_counter_is_decodes_less_restarts(tiny_model,
                                                     served_store):
    """``serving_decode_overlapped_total`` counts the decodes dispatched
    while the previous step's tokens were still on the device: every
    decode step but those that restart decoding after a step without one
    (the first, and the first after an idle spell)."""
    cfg, model, params = tiny_model
    tel = Telemetry()
    eng = MultiLoRAEngine(model, params, served_store, cache_capacity=64,
                          max_rows=2, telemetry=tel)
    decodes = []
    for burst in range(2):                        # idle between the bursts
        for r in _sched_requests(cfg):
            r.request_id += 10 * burst
            eng.submit(r)
        while eng.pending or eng.active_rows:
            before = eng.stats()["decode_steps"]
            eng.step()
            decodes.append(eng.stats()["decode_steps"] - before)
        decodes.append(0)
    restarts = sum(1 for prev, d in zip([0] + decodes, decodes)
                   if d and not prev)
    reg = tel.registry
    assert restarts >= 2 and sum(decodes) > restarts
    assert reg.value("serving_decode_steps_total") == sum(decodes)
    assert reg.value("serving_decode_overlapped_total") \
        == sum(decodes) - restarts
    overlapped = [s.counts["overlapped"] for s in tel.spans
                  if s.name == "engine.decode"]
    assert len(overlapped) == sum(decodes)
    assert sum(overlapped) == sum(decodes) - restarts


def test_discarded_tokens_count_the_eos_tail(tiny_model, served_store):
    """``serving_decode_discarded_tokens_total`` counts one token for each
    row that reads back its EOS with decodes still left in its budget (the
    decode dispatched ahead for it), and none for a row whose EOS is its
    last budgeted token."""
    cfg, model, params = tiny_model
    free = _free_outputs(cfg, model, params, served_store)
    reqs = [dataclasses.replace(r, eos_id=int(free[r.request_id][1]))
            for r in _sched_requests(cfg)]
    cuts = {r.request_id: int(np.nonzero(free[r.request_id]
                                          == r.eos_id)[0][0])
            for r in reqs}
    tail = sum(cuts[r.request_id] < r.max_new_tokens - 1 for r in reqs)
    tel = Telemetry()
    eng = MultiLoRAEngine(model, params, served_store, cache_capacity=64,
                          max_rows=2, telemetry=tel)
    for r in reqs:
        eng.submit(r)
    done = {r.request_id: r.output for r in eng.run()}
    for rid, cut in cuts.items():
        np.testing.assert_array_equal(done[rid], free[rid][: cut + 1])
    assert tail >= 2                              # both long rows stop early
    assert tel.registry.value("serving_decode_discarded_tokens_total") \
        == tail


def test_left_padded_batch_matches_unpadded_serving(tiny_model, served_store):
    """Pad-masked attention behavior fix: a left-padded row of a
    mixed-length batch now yields exactly what genuinely unpadded serving
    (no pad slots at all, direct model calls) produces."""
    cfg, model, params = tiny_model
    reqs = _mk_requests(cfg, 2, 1, seed=33, prompt_lens=[8, 5],
                        max_new=[3, 3])
    for r in reqs:
        r.adapter_id = "u0"
    lora = served_store.materialize("u0", params["lora"])
    p = {"base": params["base"], "lora": lora}

    def unpadded(prompt, n_new):
        toks = jnp.asarray(np.asarray(prompt)[None].astype(np.int32))
        logits, caches = model.prefill(p, {"tokens": toks}, 64)
        out = [int(jnp.argmax(logits[0, -1]))]
        pos = len(prompt)
        for _ in range(n_new - 1):
            logits, caches = model.decode_step(
                p, jnp.asarray([[out[-1]]], jnp.int32), caches,
                jnp.int32(pos))
            out.append(int(jnp.argmax(logits[0, -1])))
            pos += 1
        return np.asarray(out, np.int32)

    want = {r.request_id: unpadded(r.prompt, r.max_new_tokens) for r in reqs}
    eng = MultiLoRAEngine(model, params, served_store, cache_capacity=64)
    for r in reqs:
        eng.submit(r)
    got = {r.request_id: r.output for r in eng.run(mode="materialize")}
    for rid in want:                 # incl. the left-padded 5-token prompt
        np.testing.assert_array_equal(got[rid], want[rid])


def test_moe_extra_lead_dims_packed_parity():
    """MoE per-expert adapter leaves ((L, E, r, in)) are served PACKED: the
    expert axis folds into the adapter axis of the SGMV stack (no fp
    materialization, no fallback warning), token-for-token equal to the fp
    segment-loop reference.

    The capacity factor is raised to n_experts so no token-choice capacity
    drop occurs: drops are batch-composition-dependent (the materialize
    reference batches per adapter, packed batches all rows together), so
    exact cross-mode parity is only defined drop-free."""
    cfg = smoke_cfg("mixtral-8x22b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert any(np.ndim(leaf["a"]) != 3
               for _, leaf in iter_lora_linears(params["lora"]))
    store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    for i in range(2):                   # two adapters: fold × seg interplay
        store.register(f"moe_u{i}", random_trained_lora(
            params["lora"], jax.random.PRNGKey(7 + i), scale=0.05))
    engine = MultiLoRAEngine(model, params, store, cache_capacity=32)

    import warnings as _w

    def batch():
        return _mk_requests(cfg, 3, 2, seed=3, prompt_lens=[8, 8, 8],
                            max_new=[2, 3, 2])

    for r in batch():
        r.adapter_id = f"moe_u{r.request_id % 2}"
        engine.submit(r)
    with _w.catch_warnings():
        _w.simplefilter("error")                  # no fallback warning
        done = engine.run()                       # default continuous mode
    cont = {r.request_id: r.output for r in done}
    assert len(cont) == 3
    assert store.fp_resident_bytes() == 0         # served from packed codes

    for r in batch():
        r.adapter_id = f"moe_u{r.request_id % 2}"
        engine.submit(r)
    ref = {r.request_id: r.output
           for r in engine.run(mode="materialize")}
    assert store.fp_resident_bytes() > 0
    assert cont.keys() == ref.keys()
    for rid in ref:
        np.testing.assert_array_equal(cont[rid], ref[rid])


def test_unregister_removes_adapter_and_caches(tiny_model):
    """AdapterStore.unregister: the adapter stops being admittable, every
    cache tier (fp LRU, packed layouts, batch trees) drops it, and the
    paged memory reconciles on the next step."""
    cfg, model, params = tiny_model
    store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    for i in range(2):
        store.register(f"u{i}", random_trained_lora(
            params["lora"], jax.random.PRNGKey(80 + i)))
    engine = MultiLoRAEngine(model, params, store, cache_capacity=64)
    for r in _mk_requests(cfg, 2, 2, seed=5):
        engine.submit(r)
    assert len(engine.run()) == 2
    store.materialize("u0", params["lora"])       # populate the fp LRU too
    assert engine.memory.resident("u0")

    store.unregister("u0")
    assert "u0" not in store.quantized and store.version("u0") is None
    assert len(store._lru) == 0                   # fp LRU entry dropped
    assert store.packed_cache_bytes() == 0
    with pytest.raises(KeyError):
        store.unregister("u0")                    # double-free is an error
    # a new request for the dropped adapter is REJECTED at submit with the
    # structured UnknownAdapter error (not a KeyError deep in admission)
    rej = engine.submit(_mk_requests(cfg, 1, 1, seed=6)[0])
    assert rej.status is RequestStatus.REJECTED
    assert isinstance(rej.error, UnknownAdapter)
    assert rej.error.adapter_id == "u0" and rej.output.size == 0
    assert not engine.pending                     # never enqueued
    # the paged tier frees the slot and host page on its next step
    req = _mk_requests(cfg, 1, 1, seed=7)[0]
    req.adapter_id = "u1"
    engine.submit(req)
    assert len(engine.run()) == 1
    assert not engine.memory.resident("u0")
    assert "u0" not in engine.memory._host


def test_reregister_after_unregister_serves_new_weights(tiny_model):
    """Regression for the unregister lifecycle: unregister + register of
    the same id must serve the NEW weights through the paged packed path
    (a stale page or pack-cache entry would silently serve the old user)."""
    cfg, model, params = tiny_model
    t_old = random_trained_lora(params["lora"], jax.random.PRNGKey(85),
                                scale=0.05)
    t_new = random_trained_lora(params["lora"], jax.random.PRNGKey(86),
                                scale=0.05)
    req = lambda: _mk_requests(cfg, 1, 1, seed=9)[0]

    store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    store.register("u0", t_old)
    engine = MultiLoRAEngine(model, params, store, cache_capacity=64)
    engine.submit(req())
    engine.run()                                  # page for t_old resident
    store.unregister("u0")
    store.register("u0", t_new)                   # the user re-uploads
    engine.submit(req())
    got = engine.run()[0].output

    fresh_store = AdapterStore(LoRAQuantConfig(rho=0.9, ste_steps=0))
    fresh_store.register("u0", t_new)
    fresh = MultiLoRAEngine(model, params, fresh_store, cache_capacity=64)
    fresh.submit(req())
    np.testing.assert_array_equal(got, fresh.run()[0].output)


def test_train_driver_smoke(tmp_path):
    from repro.launch.train import main

    params = main([
        "--arch", "olmo-1b", "--steps", "8", "--batch", "2", "--seq", "32",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "4", "--log-every", "100",
    ])
    assert params is not None
    import os
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))


def test_serve_driver_smoke(capsys):
    from repro.launch.serve import main

    done = main(["--arch", "llama3.2-3b", "--adapters", "2", "--requests", "2",
                 "--prompt-len", "8", "--max-new", "2"])
    assert len(done) == 2
