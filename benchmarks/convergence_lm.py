"""Real convergence run, in-tree: train a small Llama on a procedurally
generated char-level corpus with a KNOWN entropy floor, and evaluate on
HELD-OUT data (ref methodology: test/legacy_test/test_dist_base.py:952
loss-curve checks; this run replaces "overfit one batch" evidence with
train/eval curves against an analytic target).

The source is an order-2 Markov chain over a 32-symbol alphabet with a
fixed seeded Dirichlet(0.3) transition table. Its conditional entropy
H = -sum_s pi(s) sum_c P(c|s) log P(c|s) is computable exactly, so the
eval target is principled: a model that reaches eval cross-entropy
<= 1.05 * H has LEARNED the source (the unigram floor is ~log 32 =
3.47 nats; memorization cannot help on the held-out stream).

Run on the real chip:

    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/convergence_lm.py

The CI-short variant lives in tests/test_convergence.py (same
generator, smaller model/steps, looser target).
"""
import json
import time

import numpy as np

VOCAB = 32


def make_chain(seed: int = 0, concentration: float = 0.3, order: int = 2):
    """[VOCAB^order, VOCAB] transition table + its stationary entropy.

    ``order=1`` (32-state table) learns in a couple hundred steps — the
    CI-short test's regime; ``order=2`` (1024 states) needs real data
    efficiency and is the benchmark regime."""
    rng = np.random.RandomState(seed)
    n_states = VOCAB ** order
    trans = rng.dirichlet(np.full(VOCAB, concentration), size=n_states)
    pi = np.full(n_states, 1.0 / n_states)
    for _ in range(400):
        if order == 1:
            nxt = pi @ trans
        else:
            # mass of state (a,b) flows to states (b, :)
            flow = pi[:, None] * trans  # [ab, c]
            nxt = flow.reshape(VOCAB, VOCAB, VOCAB).sum(0).reshape(-1)
        if np.abs(nxt - pi).max() < 1e-12:
            pi = nxt
            break
        pi = nxt
    h = float(-(pi[:, None] * trans * np.log(trans + 1e-30)).sum())
    return trans, h


def sample_stream(trans, n: int, seed: int, order: int = 2) -> np.ndarray:
    """Sample n tokens from the chain (its own RNG — train seed 1,
    eval seed 2 give DISJOINT streams)."""
    rng = np.random.RandomState(seed)
    out = np.empty(n, np.int32)
    a, b = rng.randint(0, VOCAB), rng.randint(0, VOCAB)
    # cumulative tables once; inverse-CDF sampling per step
    cum = np.cumsum(trans, axis=1)
    u = rng.rand(n)
    for i in range(n):
        state = (a * VOCAB + b) if order == 2 else b
        c = int(np.searchsorted(cum[state], u[i]))
        c = min(c, VOCAB - 1)
        out[i] = c
        a, b = b, c
    return out


def batches(stream: np.ndarray, batch: int, seq: int, rng: np.random.RandomState):
    """Random [batch, seq+1] windows -> (inputs, labels)."""
    starts = rng.randint(0, len(stream) - seq - 1, size=batch)
    wins = np.stack([stream[s:s + seq + 1] for s in starts])
    return wins[:, :-1].astype(np.int64), wins[:, 1:].astype(np.int64)


def run(hidden=256, layers=4, heads=4, batch=32, seq=128,
        steps=600, eval_every=100, lr=3e-3, train_tokens=400_000,
        eval_tokens=50_000, target_ratio=1.05, order=2, log=print,
        bf16_sr=False):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.tensor import manipulation as M

    trans, h_floor = make_chain(order=order)
    train = sample_stream(trans, train_tokens, seed=1, order=order)
    heldout = sample_stream(trans, eval_tokens, seed=2, order=order)
    log(f"source entropy floor H = {h_floor:.4f} nats "
        f"(unigram ~{np.log(VOCAB):.4f}); target eval CE <= "
        f"{target_ratio:.2f}*H = {target_ratio * h_floor:.4f}")

    paddle.seed(0)
    cfg = LlamaConfig(
        vocab_size=VOCAB, hidden_size=hidden,
        intermediate_size=int(hidden * 8 / 3) // 64 * 64 or 128,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=heads, max_position_embeddings=max(seq, 256),
    )
    model = LlamaForCausalLM(cfg)
    if bf16_sr:
        # masterless bf16 with stochastic-rounded writes: the full-lr
        # trajectory without fp32 masters (validated against the f32
        # run's eval target)
        model.bfloat16()
    opt = popt.AdamW(learning_rate=lr, parameters=model.parameters(),
                     weight_decay=0.01, use_stochastic_rounding=bf16_sr)

    def step_fn(x, y):
        logits = model(x)
        b, s, v = logits.shape
        loss = F.cross_entropy(
            M.reshape(logits, [b * s, v]), M.reshape(y, [b * s]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    import paddle_tpu.jit as pjit

    train_step = pjit.to_static(step_fn, layers=[model], optimizers=[opt])

    def eval_loss():
        from paddle_tpu.base.tape import no_grad

        rng = np.random.RandomState(99)
        tot, n = 0.0, 0
        with no_grad():
            for _ in range(8):
                x, y = batches(heldout, batch, seq, rng)
                logits = model(paddle.to_tensor(x))
                b, s, v = logits.shape
                ce = F.cross_entropy(
                    M.reshape(logits, [b * s, v]),
                    M.reshape(paddle.to_tensor(y), [b * s]))
                tot += float(ce)
                n += 1
        return tot / n

    rng = np.random.RandomState(7)
    curve = []
    t0 = time.time()
    for step in range(1, steps + 1):
        x, y = batches(train, batch, seq, rng)
        loss = train_step(paddle.to_tensor(x), paddle.to_tensor(y))
        if step % eval_every == 0 or step == steps:
            ev = eval_loss()
            curve.append({"step": step, "train": round(float(loss), 4),
                          "eval": round(ev, 4)})
            log(f"step {step:5d}  train {float(loss):.4f}  eval {ev:.4f}  "
                f"(floor {h_floor:.4f})  {time.time()-t0:.0f}s")
    final_eval = curve[-1]["eval"]
    ok = final_eval <= target_ratio * h_floor
    result = {
        "metric": "eval_ce_over_entropy_floor",
        "value": round(final_eval / h_floor, 4),
        "floor_nats": round(h_floor, 4),
        "final_eval_ce": round(final_eval, 4),
        "target": target_ratio,
        "reached": bool(ok),
        "curve": curve,
    }
    log(json.dumps(result))
    return result


if __name__ == "__main__":
    import os

    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    # the recorded convergence config (lr 1e-2 DIVERGES at this width
    # — sits at unigram).
    # CONV_BF16_SR=1 reruns it in masterless-bf16 stochastic-rounding
    # mode (same lr/steps — the point is trajectory parity).
    run(hidden=256, layers=4, heads=4, batch=64, seq=128,
        steps=3000, eval_every=500, lr=3e-3,
        train_tokens=2_000_000, eval_tokens=100_000,
        target_ratio=1.05, order=2,
        bf16_sr=os.environ.get("CONV_BF16_SR") == "1")
