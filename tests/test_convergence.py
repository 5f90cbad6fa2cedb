"""CI-short convergence checks on held-out data (ref: SURVEY §4
convergence-style tests; the full runs with curves live in
benchmarks/convergence_lm.py and benchmarks/convergence_resnet.py and
their measured results).

These are REAL learning checks, not overfit-one-batch: eval streams
are disjoint from training, and the LM target is relative to the
source's analytic entropy floor."""
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "benchmarks"))


class TestMarkovLMConvergence:
    def test_small_llama_approaches_entropy_floor(self):
        from convergence_lm import VOCAB, run

        result = run(hidden=128, layers=2, heads=4, batch=16, seq=64,
                     steps=200, eval_every=200, lr=1e-2,
                     train_tokens=120_000, eval_tokens=20_000,
                     target_ratio=1.15, order=1, log=lambda *a: None)
        floor = result["floor_nats"]
        final = result["final_eval_ce"]
        # must clearly beat the unigram baseline (proves context use)...
        assert final < 0.85 * np.log(VOCAB), (final, np.log(VOCAB))
        # ...and be within 30% of the analytic floor on HELD-OUT data
        assert result["reached"], (final, floor)


    def test_small_llama_bf16_sr_matches_f32_target(self):
        """Masterless bf16 + stochastic rounding must reach the same
        held-out entropy-floor target as the f32 run (trajectory
        parity is the point of SR — no fp32 masters anywhere)."""
        from convergence_lm import run

        result = run(hidden=128, layers=2, heads=4, batch=16, seq=64,
                     steps=200, eval_every=200, lr=1e-2,
                     train_tokens=120_000, eval_tokens=20_000,
                     target_ratio=1.15, order=1, log=lambda *a: None,
                     bf16_sr=True)
        assert result["reached"], (result["final_eval_ce"],
                                   result["floor_nats"])

class TestResNetConvergence:
    def test_small_cnn_learns_textures_heldout(self):
        import paddle_tpu.nn as nn

        from convergence_resnet import run

        def tiny_cnn(num_classes):
            return nn.Sequential(
                nn.Conv2D(3, 16, 5, stride=2, padding=2), nn.ReLU(),
                nn.Conv2D(16, 32, 3, stride=2, padding=1), nn.ReLU(),
                nn.AdaptiveAvgPool2D(1), nn.Flatten(),
                nn.Linear(32, num_classes),
            )

        result = run(num_classes=4, size=24, train_n=1500, eval_n=400,
                     batch=64, steps=150, eval_every=150, lr=2e-3,
                     target_acc=0.85, model_fn=tiny_cnn,
                     log=lambda *a: None)
        assert result["reached"], result["curve"]
