"""Operations and bytes that a step needs, counted from shapes alone.

"Needed" means what any implementation of the step has to do, whatever
the program chooses: every weight read once per call, the valid cache
entries read, the new entries written, the matrix products of the rows
that hold a request, causal attention over the lower triangle, and one
output row per prompt.  Each count is for bfloat16 weights and KV cache
(2 bytes) and float32 recurrent state (4 bytes), as the configurations
state.  Mamba-2's scan is counted as the chunked SSD algorithm at the
configuration's published ``chunk_size``, not at any chunk the program
picks, so a change of chunk cannot move the yardstick.
"""
from __future__ import annotations

from typing import NamedTuple

BF16, F32 = 2, 4


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other):
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, peak: dict) -> float:
        """The least time the chip could take: the larger of the compute
        and the memory bound."""
        return max(self.flops / peak["bf16_flop_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def _dense(cfg: dict):
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    mat = L * (2 * d * H * hd + 2 * d * Hkv * hd + 3 * d * ff)
    norms = L * 2 * d + d
    kv_token = L * 2 * Hkv * hd * BF16            # cache bytes of one position
    return d, L, H, hd, V, mat, norms, kv_token


def _mamba2(cfg: dict):
    s = cfg["ssm_cfg"]
    d, L, V = cfg["d_model"], cfg["n_layer"], cfg["vocab_size"]
    ns, K, hp, ng = s["d_state"], s["d_conv"], s["headdim"], s["ngroups"]
    di = s["expand"] * d
    nh = di // hp
    cd = di + 2 * ng * ns
    mat = L * (d * (2 * di + 2 * ng * ns + nh) + di * d)
    small_bf16 = L * (K * cd + cd + di + d) + d     # conv, norms
    small_f32 = L * 3 * nh                          # A_log, dt_bias, D
    state_row = L * (nh * ns * hp * F32 + (K - 1) * cd * BF16)
    return d, L, V, ns, K, hp, ng, nh, cd, mat, small_bf16, small_f32, state_row


def decode_step(cfg: dict, rows: int, ctx_sum: int) -> Work:
    """One decode step for ``rows`` rows that hold a request; ``ctx_sum``
    is the sum over those rows of the positions already in the cache."""
    if cfg["reference"] == "dense":
        d, L, H, hd, V, mat, norms, kv_token = _dense(cfg)
        keys = ctx_sum + rows                       # the new key is attended
        flops = rows * 2 * (mat + d * V) + L * 4 * H * hd * keys
        weights = (mat + norms + d * V) * BF16
        nbytes = (weights + rows * d * BF16 + kv_token * ctx_sum
                  + kv_token * rows)
        return Work(flops, nbytes)
    d, L, V, ns, K, hp, ng, nh, cd, mat, sb, sf, state_row = _mamba2(cfg)
    flops = rows * (2 * (mat + d * V)
                    + L * (2 * K * cd + 5 * nh * ns * hp))
    weights = (mat + sb + d * V) * BF16 + sf * F32
    nbytes = weights + rows * (d * BF16 + 2 * state_row)
    return Work(flops, nbytes)


def prefill_call(cfg: dict, rows: int, prompt_len: int) -> Work:
    """One prefill of ``rows`` refilled rows of ``prompt_len`` tokens; rows
    that the call computes but no request needs are not counted."""
    S = prompt_len
    if cfg["reference"] == "dense":
        d, L, H, hd, V, mat, norms, kv_token = _dense(cfg)
        flops = rows * (2 * mat * S + L * 4 * H * hd * S * (S + 1) // 2
                        + 2 * d * V)
        weights = (mat + norms + d * V) * BF16
        nbytes = weights + rows * S * (d * BF16 + kv_token)
        return Work(flops, nbytes)
    d, L, V, ns, K, hp, ng, nh, cd, mat, sb, sf, state_row = _mamba2(cfg)
    c = min(cfg["ssm_cfg"]["chunk_size"], S)
    # per token and layer: C B^T once per group (heads of a group share
    # B and C), the masked product with x, the chunk state and its output
    ssd = ng * 2 * c * ns + nh * (2 * c * hp + 4 * ns * hp)
    flops = rows * (S * (2 * mat + L * (2 * K * cd + ssd)) + 2 * d * V)
    weights = (mat + sb + d * V) * BF16 + sf * F32
    nbytes = weights + rows * (S * d * BF16 + state_row)
    return Work(flops, nbytes)
