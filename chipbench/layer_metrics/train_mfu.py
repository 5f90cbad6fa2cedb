"""Model FLOP/s utilisation: tokens/s x the family's FLOPs per token
(chipbench/shapes.py: 6 x matmul parameters + causal attention, no
recomputation) / (chips x the bf16 peak of chipbench/peaks.py). Divides
by the FLOP bound."""


def read(facts):
    if not facts["on_chip"] or "tokens_per_s" not in facts:
        return None
    per_token = facts["family"].train_flops_per_token(
        facts["config"], facts["seq"])
    peak = facts["chips"] * facts["peaks"].bf16_flops
    return 100.0 * facts["tokens_per_s"] * per_token / peak
