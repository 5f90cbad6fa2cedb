"""Stable-Diffusion-style conditional UNet (BASELINE.json config #5).

ref: the reference runs SD through PPDiffusers' UNet2DConditionModel
(downstream of this repo); the in-repo surface it exercises is conv2d,
GroupNorm, SiLU, and the attention entry
(nn/functional/flash_attention.py scaled_dot_product_attention).

TPU-native assembly rules: NCHW convs lowered by XLA onto the MXU;
self/cross attention reshaped to [B, HW, heads, dim] so it rides the
Pallas flash kernel when shapes qualify; sinusoidal timestep embedding
computed with static shapes; GroupNorm in f32 for bf16 stability.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .. import nn
from ..base.tape import apply
from ..nn import functional as F
from ..tensor import manipulation as M

__all__ = ["UNetConfig", "UNet2DConditionModel"]


@dataclasses.dataclass
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_out_channels: Tuple[int, ...] = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    attention_head_dim: int = 64
    cross_attention_dim: int = 768
    norm_num_groups: int = 32
    attn_resolutions: Tuple[int, ...] = (1, 2, 3)  # block indices with attn

    @classmethod
    def tiny(cls):
        return cls(
            in_channels=4, out_channels=4, block_out_channels=(32, 64),
            layers_per_block=1, attention_head_dim=16,
            cross_attention_dim=32, norm_num_groups=8,
            attn_resolutions=(1,),
        )


def timestep_embedding(t, dim: int, max_period: float = 10000.0):
    """Sinusoidal embedding (the SD convention)."""

    def f(tt):
        half = dim // 2
        freqs = jnp.exp(
            -math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half
        )
        args = tt.astype(jnp.float32)[:, None] * freqs[None, :]
        return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)

    return apply(f, t, op_name="timestep_embedding")


class ResBlock(nn.Layer):
    def __init__(self, in_c, out_c, temb_c, groups):
        super().__init__()
        self.norm1 = nn.GroupNorm(min(groups, in_c), in_c)
        self.conv1 = nn.Conv2D(in_c, out_c, 3, padding=1)
        self.temb_proj = nn.Linear(temb_c, out_c)
        self.norm2 = nn.GroupNorm(min(groups, out_c), out_c)
        self.conv2 = nn.Conv2D(out_c, out_c, 3, padding=1)
        self.skip = nn.Conv2D(in_c, out_c, 1) if in_c != out_c else None

    def forward(self, x, temb):
        h = self.conv1(F.silu(self.norm1(x)))
        h = h + M.reshape(self.temb_proj(F.silu(temb)), [x.shape[0], -1, 1, 1])
        h = self.conv2(F.silu(self.norm2(h)))
        return h + (self.skip(x) if self.skip is not None else x)


class SpatialTransformer(nn.Layer):
    """Self-attn + cross-attn + geglu FFN on flattened HW tokens."""

    def __init__(self, channels, head_dim, context_dim, groups):
        super().__init__()
        self.num_heads = max(1, channels // head_dim)
        self.head_dim = channels // self.num_heads
        self.norm = nn.GroupNorm(min(groups, channels), channels)
        self.proj_in = nn.Linear(channels, channels)
        self.norm1 = nn.LayerNorm(channels)
        self.to_qkv = nn.Linear(channels, 3 * channels, bias_attr=False)
        self.to_out1 = nn.Linear(channels, channels)
        self.norm2 = nn.LayerNorm(channels)
        self.to_q2 = nn.Linear(channels, channels, bias_attr=False)
        self.to_kv2 = nn.Linear(context_dim, 2 * channels, bias_attr=False)
        self.to_out2 = nn.Linear(channels, channels)
        self.norm3 = nn.LayerNorm(channels)
        self.ff1 = nn.Linear(channels, 4 * channels)
        self.ff2 = nn.Linear(4 * channels, channels)
        self.proj_out = nn.Linear(channels, channels)

    def _attn(self, q, k, v, b, s_kv):
        sq = q.shape[1]
        q = M.reshape(q, [b, sq, self.num_heads, self.head_dim])
        k = M.reshape(k, [b, s_kv, self.num_heads, self.head_dim])
        v = M.reshape(v, [b, s_kv, self.num_heads, self.head_dim])
        out = F.scaled_dot_product_attention(q, k, v, is_causal=False,
                                             training=self.training)
        return M.reshape(out, [b, sq, self.num_heads * self.head_dim])

    def forward(self, x, context):
        b, c, h, w = x.shape
        residual = x
        t = M.reshape(self.norm(x), [b, c, h * w])
        t = M.transpose(t, [0, 2, 1])  # [B, HW, C]
        t = self.proj_in(t)

        # self attention
        qkv = self.to_qkv(self.norm1(t))
        q, k, v = M.split(qkv, 3, axis=-1)
        t = t + self.to_out1(self._attn(q, k, v, b, h * w))
        # cross attention over the conditioning sequence
        q2 = self.to_q2(self.norm2(t))
        kv = self.to_kv2(context)
        k2, v2 = M.split(kv, 2, axis=-1)
        t = t + self.to_out2(self._attn(q2, k2, v2, b, context.shape[1]))
        # ffn
        t = t + self.ff2(F.gelu(self.ff1(self.norm3(t))))

        t = self.proj_out(t)
        t = M.transpose(t, [0, 2, 1])
        return M.reshape(t, [b, c, h, w]) + residual


class UNet2DConditionModel(nn.Layer):
    """Down blocks → mid (res+attn+res) → up blocks with skips."""

    def __init__(self, config: Optional[UNetConfig] = None, **kwargs):
        super().__init__()
        if config is not None and kwargs:
            raise ValueError(
                "pass either a UNetConfig or field kwargs, not both "
                f"(got config and {sorted(kwargs)})"
            )
        config = config or UNetConfig(**kwargs)
        self.config = config
        chs = config.block_out_channels
        temb_c = chs[0] * 4
        g = config.norm_num_groups

        self.time_embed = nn.Sequential(
            nn.Linear(chs[0], temb_c), nn.Silu(), nn.Linear(temb_c, temb_c)
        )
        self.conv_in = nn.Conv2D(config.in_channels, chs[0], 3, padding=1)

        # down
        self.down_res = nn.LayerList()
        self.down_attn = nn.LayerList()
        self.downsamplers = nn.LayerList()
        skip_chs = [chs[0]]
        in_c = chs[0]
        for i, out_c in enumerate(chs):
            for _ in range(config.layers_per_block):
                self.down_res.append(ResBlock(in_c, out_c, temb_c, g))
                self.down_attn.append(
                    SpatialTransformer(out_c, config.attention_head_dim,
                                       config.cross_attention_dim, g)
                    if i in config.attn_resolutions
                    else None
                )
                in_c = out_c
                skip_chs.append(out_c)
            if i < len(chs) - 1:
                self.downsamplers.append(nn.Conv2D(out_c, out_c, 3, stride=2, padding=1))
                skip_chs.append(out_c)

        # mid
        self.mid_res1 = ResBlock(in_c, in_c, temb_c, g)
        self.mid_attn = SpatialTransformer(
            in_c, config.attention_head_dim, config.cross_attention_dim, g
        )
        self.mid_res2 = ResBlock(in_c, in_c, temb_c, g)

        # up
        self.up_res = nn.LayerList()
        self.up_attn = nn.LayerList()
        self.upsamplers = nn.LayerList()
        for i, out_c in reversed(list(enumerate(chs))):
            for _ in range(config.layers_per_block + 1):
                skip = skip_chs.pop()
                self.up_res.append(ResBlock(in_c + skip, out_c, temb_c, g))
                self.up_attn.append(
                    SpatialTransformer(out_c, config.attention_head_dim,
                                       config.cross_attention_dim, g)
                    if i in config.attn_resolutions
                    else None
                )
                in_c = out_c
            if i > 0:
                self.upsamplers.append(nn.Conv2D(out_c, out_c, 3, padding=1))

        self.norm_out = nn.GroupNorm(min(g, chs[0]), chs[0])
        self.conv_out = nn.Conv2D(chs[0], config.out_channels, 3, padding=1)

    def forward(self, sample, timestep, encoder_hidden_states):
        """sample [B, C, H, W]; timestep [B]; context [B, L, D]."""
        config = self.config
        emb = timestep_embedding(timestep, config.block_out_channels[0])
        # the sinusoid is computed in f32; run the rest of the net in the
        # parameter dtype (bf16 under model.bfloat16())
        emb = emb.astype(self.conv_in.weight.dtype)
        temb = self.time_embed(emb)

        x = self.conv_in(sample)
        skips = [x]
        li = 0
        n_down = len(config.block_out_channels)
        for i in range(n_down):
            for _ in range(config.layers_per_block):
                x = self.down_res[li](x, temb)
                if self.down_attn[li] is not None:
                    x = self.down_attn[li](x, encoder_hidden_states)
                skips.append(x)
                li += 1
            if i < n_down - 1:
                x = self.downsamplers[i](x)
                skips.append(x)

        x = self.mid_res1(x, temb)
        x = self.mid_attn(x, encoder_hidden_states)
        x = self.mid_res2(x, temb)

        li = 0
        for j, i in enumerate(reversed(range(n_down))):
            for _ in range(config.layers_per_block + 1):
                x = M.concat([x, skips.pop()], axis=1)
                x = self.up_res[li](x, temb)
                if self.up_attn[li] is not None:
                    x = self.up_attn[li](x, encoder_hidden_states)
                li += 1
            if i > 0:
                x = F.interpolate(x, scale_factor=2, mode="nearest")
                x = self.upsamplers[j](x)

        return self.conv_out(F.silu(self.norm_out(x)))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())
