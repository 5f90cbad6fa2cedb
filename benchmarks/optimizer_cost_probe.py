"""Flagship step with SGD instead of AdamW: the delta vs the AdamW
step isolates the optimizer's HBM-roofline cost (step decomposition
by substitution). Run on the real chip with PYTHONPATH set."""
import time
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as popt
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.tensor import manipulation as M
from paddle_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache()
config = LlamaConfig(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
                     num_hidden_layers=8, num_attention_heads=16, num_key_value_heads=16,
                     max_position_embeddings=2048)
paddle.seed(0)
model = LlamaForCausalLM(config)
model.bfloat16()
opt = popt.SGD(learning_rate=1e-4, parameters=model.parameters())

def step(ids, labels):
    logits = model(ids)
    b, s, v = logits.shape
    loss = F.cross_entropy(M.reshape(logits, [b*s, v]), M.reshape(labels, [b*s]))
    loss.backward(); opt.step(); opt.clear_grad()
    return loss

compiled = paddle.jit.to_static(step, layers=[model], optimizers=[opt])
rng = np.random.RandomState(0)
ids = paddle.to_tensor(rng.randint(0, config.vocab_size, (4, 2048)).astype("int32"))
compiled(ids, ids)
np.asarray(compiled.multi_step(ids, ids, steps=4)._data)
np.asarray(compiled.multi_step(ids, ids, steps=24)._data)
def t(k):
    best = 1e9
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(compiled.multi_step(ids, ids, steps=k)._data)
        best = min(best, time.perf_counter() - t0)
    return best
ms = (t(24) - t(4)) / 20 * 1e3
print("SGD step ms:", round(ms, 2), "-> AdamW tax ~", round(202.5 - ms, 1), "ms")
