"""Largest share of the KV pool's blocks in use: 1 - min(free blocks) /
blocks, sampled after every engine step."""


def read(facts):
    if "free_blocks_min" not in facts:
        return None
    return 100.0 * (1.0 - facts["free_blocks_min"] / facts["num_blocks"])
