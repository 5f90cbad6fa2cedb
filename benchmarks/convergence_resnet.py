"""ResNet convergence on a procedurally generated, HELD-OUT-able image
task (BASELINE config #1 was "blocked on data (no egress)" — this
replaces it with synthetic-but-learnable data requiring real feature
learning, evaluated on a disjoint test set).

Task: 10-class texture classification. Class k's images are oriented
sinusoidal gratings with class-specific (frequency, orientation) plus
per-image random phase, offset, and Gaussian noise (SNR < 1) — a
linear probe on raw pixels fails (random phase decorrelates pixels
from the class), a convnet learns the spectral signature. Train and
eval sets are generated from different seeds.

Run on the real chip:

    PYTHONPATH="/root/repo:$PYTHONPATH" python benchmarks/convergence_resnet.py

CI-short variant: tests/test_convergence.py (fewer classes/steps,
smaller CNN, looser target).
"""
import json
import time

import numpy as np


def make_images(n: int, num_classes: int, size: int, seed: int):
    """[n, 3, size, size] float32 textures + [n] labels."""
    rng = np.random.RandomState(seed)
    labels = rng.randint(0, num_classes, n)
    yy, xx = np.meshgrid(np.arange(size), np.arange(size), indexing="ij")
    imgs = np.empty((n, 3, size, size), np.float32)
    for i in range(n):
        k = labels[i]
        freq = 0.6 + 0.35 * k          # class-specific frequency
        theta = (k * np.pi / num_classes) + rng.randn() * 0.05
        phase = rng.rand() * 2 * np.pi  # random phase: no fixed pixel cue
        wave = np.sin(freq * (np.cos(theta) * xx + np.sin(theta) * yy) + phase)
        base = wave[None] * np.array([1.0, 0.8, 0.6])[:, None, None]
        imgs[i] = base + rng.randn(3, size, size) * 1.2 + rng.randn() * 0.3
    return imgs.astype(np.float32), labels.astype(np.int64)


def run(num_classes=10, size=32, train_n=8000, eval_n=1000, batch=128,
        steps=600, eval_every=100, lr=1e-3, target_acc=0.95,
        model_fn=None, log=print):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as popt

    xs, ys = make_images(train_n, num_classes, size, seed=1)
    xe, ye = make_images(eval_n, num_classes, size, seed=2)

    paddle.seed(0)
    if model_fn is None:
        from paddle_tpu.vision.models import resnet18

        model = resnet18(num_classes=num_classes)
    else:
        model = model_fn(num_classes)
    opt = popt.AdamW(learning_rate=lr, parameters=model.parameters(),
                     weight_decay=1e-4)

    def step_fn(x, y):
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    import paddle_tpu.jit as pjit

    train_step = pjit.to_static(step_fn, layers=[model], optimizers=[opt])

    def eval_acc():
        from paddle_tpu.base.tape import no_grad

        model.eval()
        hits = 0
        with no_grad():
            for i in range(0, eval_n, batch):
                logits = model(paddle.to_tensor(xs_e[i:i + batch]))
                hits += int(
                    (np.asarray(logits._data).argmax(-1)
                     == ye[i:i + batch]).sum())
        model.train()
        return hits / eval_n

    xs_e = xe
    rng = np.random.RandomState(7)
    curve = []
    t0 = time.time()
    for step in range(1, steps + 1):
        idx = rng.randint(0, train_n, batch)
        loss = train_step(paddle.to_tensor(xs[idx]),
                          paddle.to_tensor(ys[idx]))
        if step % eval_every == 0 or step == steps:
            acc = eval_acc()
            curve.append({"step": step, "train_loss": round(float(loss), 4),
                          "eval_acc": round(acc, 4)})
            log(f"step {step:5d}  train {float(loss):.4f}  eval_acc "
                f"{acc:.4f}  {time.time()-t0:.0f}s")
    final = curve[-1]["eval_acc"]
    result = {
        "metric": "heldout_accuracy", "value": final,
        "target": target_acc, "reached": bool(final >= target_acc),
        "curve": curve,
    }
    log(json.dumps(result))
    return result


if __name__ == "__main__":
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
