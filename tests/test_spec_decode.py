"""Speculative decoding + int8 KV cache (ISSUE 7).

Two contracts pinned here:

1. TOKEN-EXACTNESS — speculative decoding (any k, any proposer) emits
   byte-identical greedy outputs vs ``decode_chunk=1`` / plain decode,
   across mixed-length mixed-prompt serving runs including chunked
   prefill and prefix-cache-hit slots. Accept-by-argmax-equality makes
   this hold by construction; these tests keep it held under
   refactoring.
2. INT8 KV QUALITY + SCALE CARRIAGE — quantized KV stays within an
   explicit last-logit rel-err tolerance of the bf16/f32 cache (the
   int8-weights-style gate), and COW fork / prefix-cache adoption carry the per-block
   scales with the physical block (a forked block with stale scales
   decodes garbage — the regression tests would catch it).

`pytest -m spec` runs this lane standalone.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import ContinuousBatchingEngine
from paddle_tpu.inference.speculative import (
    DraftProposer,
    NgramProposer,
    accept_length,
)
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.models.generation import generate

pytestmark = pytest.mark.spec

_RNG = np.random.RandomState(7)
_BASE = _RNG.randint(0, 50, (6,))
# repetitive prompt: n-gram lookup has signal
_REPETITIVE = np.concatenate([_BASE, _BASE, _BASE])[:16]
_PROMPTS = {
    "rep": _REPETITIVE,
    "rand": _RNG.randint(0, 250, (11,)),
    "rep2": np.concatenate([_BASE, _BASE])[:10],
}
_BUDGETS = {"rep": 10, "rand": 7, "rep2": 12}


def _model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _run_engine(prompts=None, budgets=None, eos=None, **kw):
    model = _model()
    eng = ContinuousBatchingEngine(
        model, max_batch=3, max_len=64, block_size=8, num_blocks=24,
        prompt_pad=32, eos_token_id=eos, **kw)
    for rid, p in (prompts or _PROMPTS).items():
        eng.add_request(rid, p, max_new_tokens=(budgets or _BUDGETS)[rid])
    done = eng.run()
    return {r: done[r].out for r in done}, eng


class OracleProposer(DraftProposer):
    """Proposes the request's TRUE greedy continuation (registered per
    prompt) — 100% acceptance, so multi-token emission paths and the
    stats math get exercised deterministically."""

    def __init__(self, table):
        # table: {tuple(prompt): [ref tokens...]}
        self.table = {tuple(int(t) for t in k): list(v)
                      for k, v in table.items()}

    def propose(self, tokens, k):
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        for prompt, ref in self.table.items():
            n = len(prompt)
            if toks[:n] == list(prompt):
                done = len(toks) - n
                if toks[n:] != ref[:done]:
                    break  # histories diverged (shouldn't happen)
                return np.asarray(ref[done:done + k], np.int32)
        return np.zeros((0,), np.int32)


class TestNgramProposer:
    def test_matches_most_recent_continuation(self):
        toks = np.array([5, 6, 7, 8, 5, 6, 7], np.int32)
        assert list(NgramProposer(max_ngram=3).propose(toks, 4)) == \
            [8, 5, 6, 7]

    def test_longest_ngram_wins(self):
        # tail (2, 3): bigram match at [1, 2] -> 9; but trigram
        # (1, 2, 3) also occurs earlier -> 4 must win
        toks = np.array([1, 2, 3, 4, 0, 2, 3, 9, 1, 2, 3], np.int32)
        assert int(NgramProposer(max_ngram=3).propose(toks, 1)[0]) == 4

    def test_most_recent_occurrence_wins_within_n(self):
        toks = np.array([2, 3, 4, 9, 2, 3, 5, 9, 2, 3], np.int32)
        assert int(NgramProposer(max_ngram=2).propose(toks, 1)[0]) == 5

    def test_no_match_returns_empty(self):
        assert NgramProposer().propose(
            np.arange(10, dtype=np.int32), 4).size == 0

    def test_short_history_and_k0(self):
        p = NgramProposer()
        assert p.propose(np.array([3], np.int32), 4).size == 0
        assert p.propose(np.array([3, 3, 3], np.int32), 0).size == 0

    def test_validation(self):
        with pytest.raises(ValueError, match="min_ngram"):
            NgramProposer(max_ngram=2, min_ngram=3)

    def test_accept_length(self):
        assert accept_length([1, 2, 3], [1, 2, 3, 9]) == 3
        assert accept_length([1, 2, 3], [1, 9, 3]) == 1
        assert accept_length([1], [2]) == 0
        assert accept_length(np.zeros((0,)), np.array([5])) == 0


class TestGenerateSpeculative:
    def test_token_exact_vs_plain_greedy(self):
        model = _model()
        ids = paddle.to_tensor(np.asarray(_REPETITIVE, np.int64)[None])
        ref = np.asarray(generate(model, ids, max_new_tokens=12,
                                  use_jit=False).numpy())
        for k in (2, 4, 8):
            out = np.asarray(generate(model, ids, max_new_tokens=12,
                                      speculative_k=k).numpy())
            assert (out == ref).all(), k

    def test_no_draft_rounds_fall_back_to_single_step(self, monkeypatch):
        """When no row has draft signal the round must take the plain
        decode step, not a (k+1)-wide verify that advances ~1 token —
        the engine path's zero-cost fallback, mirrored."""
        import paddle_tpu.models.generation as G

        calls = {"verify": 0}
        orig = G._get_compiled

        def wrapped(*a, **kw):
            res = orig(*a, **kw)
            if len(res) == 4:
                state, prefill, decode, verify = res

                def counting_verify(ids, cur):
                    calls["verify"] += 1
                    return verify(ids, cur)

                return state, prefill, decode, counting_verify
            return res

        monkeypatch.setattr(G, "_get_compiled", wrapped)

        class NoDraft(DraftProposer):
            def propose(self, tokens, k):
                return np.zeros((0,), np.int32)

        ids = paddle.to_tensor(
            np.asarray(_PROMPTS["rand"], np.int64)[None])
        ref = np.asarray(generate(_model(), ids,
                                  max_new_tokens=8).numpy())
        out = np.asarray(generate(_model(), ids, max_new_tokens=8,
                                  speculative_k=4,
                                  draft_proposer=NoDraft()).numpy())
        assert calls["verify"] == 0
        assert (out == ref).all()

    def test_batch_rows_advance_together_exactly(self):
        model = _model()
        both = np.stack([_REPETITIVE,
                         _RNG.randint(0, 250, (16,))]).astype(np.int64)
        ids = paddle.to_tensor(both)
        ref = np.asarray(generate(model, ids, max_new_tokens=9,
                                  use_jit=False).numpy())
        out = np.asarray(generate(model, ids, max_new_tokens=9,
                                  speculative_k=3).numpy())
        assert (out == ref).all()

    def test_eos_freezes_rows(self):
        model = _model()
        ids = paddle.to_tensor(np.asarray(_REPETITIVE, np.int64)[None])
        ref = np.asarray(generate(model, ids, max_new_tokens=10,
                                  use_jit=False).numpy())[0, 16:]
        eos = int(ref[3])
        want = list(ref[:4]) + [eos] * 6
        out = np.asarray(generate(
            model, ids, max_new_tokens=10, speculative_k=4,
            eos_token_id=eos).numpy())[0, 16:]
        assert list(out) == want

    def test_paged_int8_kv_composes(self):
        model = _model()
        ids = paddle.to_tensor(np.asarray(_REPETITIVE, np.int64)[None])
        ref8 = np.asarray(generate(model, ids, max_new_tokens=10,
                                   block_size=8, kv_dtype="int8").numpy())
        out8 = np.asarray(generate(
            model, ids, max_new_tokens=10, block_size=8, kv_dtype="int8",
            speculative_k=4).numpy())
        assert (out8 == ref8).all()

    def test_validation(self):
        model = _model()
        ids = paddle.to_tensor(np.asarray(_REPETITIVE, np.int64)[None])
        with pytest.raises(ValueError, match="greedy-only"):
            generate(model, ids, speculative_k=2, temperature=0.5)
        with pytest.raises(ValueError, match="alternative decode"):
            generate(model, ids, speculative_k=2, decode_chunk=4)
        with pytest.raises(ValueError, match="speculative_k"):
            generate(model, ids, speculative_k=0)
        with pytest.raises(ValueError, match="paged"):
            generate(model, ids, kv_dtype="int8")  # dense cache


class TestEngineSpeculative:
    def test_token_exact_whole_prompt_mode(self):
        plain, _ = _run_engine()
        for k in (2, 4):
            spec, eng = _run_engine(spec_decode_k=k)
            assert spec == plain, k
            assert eng.spec_stats()["enabled"]

    def test_token_exact_chunked_prefill_and_prefix_cache(self):
        """Cache-hit slots decode speculatively on ADOPTED blocks: two
        WAVES (the second admits after the first's blocks are cached)
        so the prefix lookup actually hits, with chunked prefill on."""

        def run(spec_k):
            model = _model()
            eng = ContinuousBatchingEngine(
                model, max_batch=2, max_len=64, block_size=8,
                num_blocks=24, prefill_chunk=8, max_num_batched_tokens=32,
                prefix_cache=True, spec_decode_k=spec_k)
            eng.add_request("rep", _PROMPTS["rep"], max_new_tokens=8)
            eng.add_request("rand", _PROMPTS["rand"], max_new_tokens=6)
            eng.run()
            eng.add_request("hit", _PROMPTS["rep"].copy(),
                            max_new_tokens=8)
            eng.add_request("hit2", _PROMPTS["rep2"].copy(),
                            max_new_tokens=6)
            done = eng.run()
            return {r: done[r].out for r in done}, eng

        plain, _ = run(None)
        spec, eng = run(4)
        assert spec == plain
        assert eng.prefix_stats()["hit_tokens"] > 0
        # the hit slots' continuation equals the cold slot's
        assert spec["hit"] == plain["rep"]

    def test_acceptance_rate_positive_on_repetitive_prompts(self):
        """A long-enough greedy run on the repetitive prompt re-quotes
        its own output (the prompt-lookup premise), so the n-gram
        proposer lands accepts — rate strictly > 0, and emitted
        strictly exceeds dispatch count (the multiplier is real)."""
        model = _model()
        eng = ContinuousBatchingEngine(
            model, max_batch=1, max_len=96, block_size=8, num_blocks=24,
            prompt_pad=32, spec_decode_k=4)
        eng.add_request("rep", _PROMPTS["rep"], max_new_tokens=48)
        eng.run()
        st = eng.spec_stats()
        assert st["proposed"] > 0
        assert st["acceptance_rate"] > 0
        assert st["tokens_per_slot_round"] > 1.0

    def test_oracle_proposer_full_accept_and_fewer_dispatches(self):
        plain, peng = _run_engine()
        oracle = OracleProposer(
            {tuple(_PROMPTS[r]): plain[r] for r in plain})
        spec, eng = _run_engine(spec_decode_k=4, draft_proposer=oracle)
        assert spec == plain
        st = eng.spec_stats()
        assert st["acceptance_rate"] == 1.0
        assert st["tokens_per_slot_round"] > 2.0
        # the whole point: strictly fewer decode dispatches than
        # one-token-per-step would need for the same tokens
        assert st["dispatches"] * (4 + 1) < sum(_BUDGETS.values())

    def test_eos_mid_accepted_prefix_stops_exactly(self):
        plain, _ = _run_engine()
        eos = plain["rep"][4]
        ref, _ = _run_engine(eos=eos)
        oracle = OracleProposer({tuple(_PROMPTS[r]): plain[r]
                                 for r in plain})
        spec, _ = _run_engine(eos=eos, spec_decode_k=4,
                              draft_proposer=oracle)
        assert spec == ref

    def test_budget_too_small_falls_back_to_plain_decode(self):
        # k+1 = 9 > budget 8: a verify round can NEVER fit — every
        # step must fall back to plain decode, tokens unchanged
        def run(spec_k):
            model = _model()
            eng = ContinuousBatchingEngine(
                model, max_batch=1, max_len=64, block_size=8,
                num_blocks=16, prefill_chunk=8, max_num_batched_tokens=8,
                spec_decode_k=spec_k)
            eng.add_request("rep", _PROMPTS["rep"], max_new_tokens=8)
            done = eng.run()
            return done["rep"].out, eng

        plain, _ = run(None)
        spec, eng = run(8)
        assert spec == plain
        assert eng.spec_stats()["dispatches"] == 0

    def test_budget_respected_under_mixed_load(self):
        """Spec runs when the leftover budget covers a verify round and
        steps never exceed the cap — exactness holds throughout."""
        plain, _ = _run_engine(prefill_chunk=8, max_num_batched_tokens=16)
        spec, eng = _run_engine(prefill_chunk=8, max_num_batched_tokens=16,
                                spec_decode_k=4)
        assert spec == plain
        assert eng.max_step_tokens <= 16

    def test_spec_telemetry_counts_real_tokens_not_positions(self):
        """The budget is charged k+1 dispatch positions per slot, but
        the service-rate EWMA (load().tokens_per_step, the admission
        delay estimate) must see the REAL emitted tokens: an
        always-wrong proposer drains 1 token/round, not k+1."""
        plain, _ = _run_engine(prompts={"rep": _PROMPTS["rep"]},
                               budgets={"rep": 10})
        ref = plain["rep"]

        class Anti(DraftProposer):
            # first draft = true-next + 1: never accepted
            def propose(self, tokens, k):
                g = len(tokens) - len(_PROMPTS["rep"])
                nxt = ref[g] if 0 <= g < len(ref) else 0
                return np.full((k,), (int(nxt) + 1) % 256, np.int32)

        out, eng = _run_engine(
            prompts={"rep": _PROMPTS["rep"]}, budgets={"rep": 10},
            prefill_chunk=8, max_num_batched_tokens=32,
            spec_decode_k=4, draft_proposer=Anti())
        assert out["rep"] == ref  # exactness even at 0% acceptance
        st = eng.spec_stats()
        assert st["dispatches"] > 0 and st["accepted"] == 0
        assert eng.max_step_tokens >= 5  # budget still charged k+1
        assert eng.ewma_step_tokens < 3  # drain rate ~1 token/round

    def test_spec_yields_budget_to_mid_prefill_slots(self):
        """Under a tight token budget a verify round (active*(k+1))
        must not eat the whole step's budget while a slot is
        mid-prefill — spec falls back to plain decode so the new
        request's prefill chunks keep landing (the scan path's
        starvation guard, applied to the spec gate)."""
        def build(spec_k, proposer=None):
            model = _model()
            eng = ContinuousBatchingEngine(
                model, max_batch=2, max_len=64, block_size=8,
                num_blocks=24, prefill_chunk=4, max_num_batched_tokens=5,
                spec_decode_k=spec_k, draft_proposer=proposer)
            eng.add_request("a", _PROMPTS["rep"], max_new_tokens=48)
            # warm until A is decode-phase (prefill done)
            while eng.num_prefilling or not any(
                    s.active for s in eng._slots):
                eng.step()
            eng.add_request("b", _PROMPTS["rand"], max_new_tokens=4)
            return eng

        plain = build(None)
        ref = {r: g.out for r, g in plain.run().items()}
        # oracle always drafts for A, so a verify round (1*(k+1) = 5
        # == budget) WOULD fit every step without the guard
        oracle = OracleProposer({tuple(_PROMPTS["rep"]): ref["a"]})
        eng = build(4, oracle)
        steps_until_b = 0
        while "b" not in eng._completed:
            eng.step()
            steps_until_b += 1
            assert steps_until_b < 12, \
                "mid-prefill slot starved by spec verify rounds"
        out = {r: g.out for r, g in eng.run().items()}
        assert out == ref

    def test_budget_accounting_counts_verify_positions(self):
        _, eng = _run_engine(prefill_chunk=8, max_num_batched_tokens=48,
                             spec_decode_k=4)
        assert eng.spec_stats()["dispatches"] > 0
        assert eng.max_step_tokens <= 48

    def test_validation(self):
        model = _model()
        with pytest.raises(ValueError, match="spec_decode_k"):
            ContinuousBatchingEngine(
                model, max_batch=1, max_len=32, block_size=8,
                num_blocks=8, spec_decode_k=0)
        with pytest.raises(ValueError, match="kv_dtype"):
            ContinuousBatchingEngine(
                model, max_batch=1, max_len=32, block_size=8,
                num_blocks=8, kv_dtype="int4")


class TestInt8KV:
    # the explicit tolerance of the quality gate: prefill last-logit
    # relative error of int8-KV vs the float cache on the tiny model
    # (same style as the int8-WEIGHTS gate, measured 0.031 at 542M)
    REL_ERR_TOL = 0.05

    def test_last_logit_rel_err_gate(self):
        from paddle_tpu import to_tensor
        from paddle_tpu.base.tape import no_grad

        model = _model()
        ids = paddle.to_tensor(
            _RNG.randint(0, 250, (2, 12)).astype(np.int64))
        with no_grad():
            cf = model.init_cache(2, 24, block_size=8)
            lf, _ = model.forward_with_cache(
                ids, cf, to_tensor(np.asarray(0, np.int32)))
            cq = model.init_cache(2, 24, block_size=8, kv_dtype="int8")
            lq, _ = model.forward_with_cache(
                ids, cq, to_tensor(np.asarray(0, np.int32)))
        a = np.asarray(lf._data[:, -1], np.float32)
        b = np.asarray(lq._data[:, -1], np.float32)
        rel = float(np.abs(a - b).mean() / (np.abs(a).mean() + 1e-9))
        assert rel < self.REL_ERR_TOL, rel

    def test_engine_matches_paged_generate_int8(self):
        """Engine (ragged tables, offset prefill) and generate()
        (contiguous tables) quantize the same values — token-identical
        under the same int8 cache."""
        out8, _ = _run_engine(kv_dtype="int8")
        model = _model()
        for rid, p in _PROMPTS.items():
            ids = paddle.to_tensor(np.asarray(p, np.int64)[None])
            want = list(np.asarray(generate(
                model, ids, max_new_tokens=_BUDGETS[rid], block_size=8,
                kv_dtype="int8", use_jit=False).numpy())[0][p.size:])
            assert out8[rid] == want, rid

    def test_prefix_adopt_carries_scales(self):
        """A cache-hit request decodes on ADOPTED int8 blocks: wrong or
        missing scales would change its tokens vs the cold run."""
        prompts = {"cold": _REPETITIVE}
        cold, _ = _run_engine(prompts=prompts,
                              budgets={"cold": 8}, kv_dtype="int8",
                              prefix_cache=True)
        both = {"cold": _REPETITIVE, "hit": _REPETITIVE.copy()}
        out, eng = _run_engine(
            prompts=both, budgets={"cold": 8, "hit": 8},
            kv_dtype="int8", prefix_cache=True)
        assert out["cold"] == cold["cold"]
        assert out["hit"] == cold["cold"]
        assert eng.prefix_stats()["hit_tokens"] > 0

    def test_cow_fork_copies_scale_rows(self):
        """Unit pin on the device copy: _copy_block must move scale
        pool rows with value pool rows."""
        model = _model()
        eng = ContinuousBatchingEngine(
            model, max_batch=1, max_len=32, block_size=8, num_blocks=4,
            kv_dtype="int8", prefix_cache=True)
        import jax.numpy as jnp

        k, v, ks, vs = eng._pools[0]
        eng._pools[0] = (
            k.at[:, 1].set(7), v.at[:, 1].set(9),
            ks.at[:, 1].set(0.5), vs.at[:, 1].set(0.25))
        eng._copy_block(1, 2)
        k2, v2, ks2, vs2 = eng._pools[0]
        assert float(jnp.abs(k2[:, 2] - 7).max()) == 0
        assert float(jnp.abs(ks2[:, 2] - 0.5).max()) == 0
        assert float(jnp.abs(vs2[:, 2] - 0.25).max()) == 0

    def test_fully_cached_prompt_fork_token_exact_int8(self):
        """The fork path (fully cached block-multiple prompt rewrites
        its last token inside a shared block) under int8: readers keep
        bytes AND scales."""
        p16 = _REPETITIVE  # 16 tokens = 2 full blocks at bs=8
        ref, _ = _run_engine(prompts={"a": p16}, budgets={"a": 6},
                             kv_dtype="int8", prefix_cache=True)
        out, eng = _run_engine(
            prompts={"a": p16, "b": p16.copy(), "c": p16.copy()},
            budgets={"a": 6, "b": 6, "c": 6},
            kv_dtype="int8", prefix_cache=True)
        for r in ("a", "b", "c"):
            assert out[r] == ref["a"], r
        assert eng.prefix_forks >= 1

    def test_alloc_validation(self):
        from paddle_tpu.ops.paged_attention import alloc_paged_kv_caches

        with pytest.raises(ValueError, match="kv_dtype"):
            alloc_paged_kv_caches(1, 1, 16, 2, 4, np.float32,
                                  block_size=8, kv_dtype="fp8")

    def test_spec_plus_int8_token_exact(self):
        """Both levers composed == int8 alone (the compounding claim)."""
        plain8, _ = _run_engine(kv_dtype="int8")
        spec8, eng = _run_engine(kv_dtype="int8", spec_decode_k=4)
        assert spec8 == plain8
        assert eng.spec_stats()["enabled"]
