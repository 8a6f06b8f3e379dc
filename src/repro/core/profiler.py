"""EdgeProfiler facade (paper Fig. 3).

Inputs: model config x hardware config x precision config.
Outputs: params, FLOPs/token, memory footprint, stage-wise latency,
end-to-end latency, arithmetic intensity, energy per token — the exact
output set listed in paper §IV "Experimental Setup".
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

from repro.core import analytical, energy as energy_mod, hardware as hw_mod
from repro.core import latency as lat_mod, precision as prec_mod
from repro.core.model_config import ModelSpec, ShapeSpec


@dataclass
class Report:
    model: str
    hardware: str
    precision: str
    seq_len: int
    params: int
    flops_per_token: float
    model_size_bytes: float
    memory_runtime_bytes: float
    latency: lat_mod.LatencyBreakdown
    arithmetic_intensity: float
    energy_per_token_j: float
    analysis: analytical.Analysis = field(repr=False, default=None)  # type: ignore[assignment]

    def as_dict(self) -> Dict[str, float]:
        return {
            "model": self.model, "hardware": self.hardware,
            "precision": self.precision, "seq_len": self.seq_len,
            "params": self.params, "flops_per_token": self.flops_per_token,
            "model_size_gb": self.model_size_bytes / 1e9,
            "memory_runtime_gb": self.memory_runtime_bytes / 1e9,
            "t_compute": self.latency.compute, "t_memory": self.latency.memory,
            "t_io": self.latency.storage_io, "t_h2d": self.latency.h2d,
            "t_net": self.latency.network, "t_end_to_end": self.latency.end_to_end,
            "t_steady": self.latency.steady_state,
            "arith_intensity": self.arithmetic_intensity,
            "energy_per_token_j": self.energy_per_token_j,
        }


# llama.cpp-style resident runtime overhead (buffers, graph, tokenizer).
_RUNTIME_OVERHEAD = 0.45e9


def profile(spec: ModelSpec, hardware: str | hw_mod.HardwareSpec = "rpi4",
            precision: str | prec_mod.PrecisionSpec = "fp16",
            seq_len: int = 2048, batch: int = 1,
            kind: str = "decode") -> Report:
    """Run the analytical pipeline for one (model, device, precision) cell."""
    hw = hw_mod.get(hardware) if isinstance(hardware, str) else hardware
    prec = prec_mod.get(precision) if isinstance(precision, str) else precision
    shape = ShapeSpec(f"s{seq_len}b{batch}", seq_len, batch, kind)

    an = analytical.analyze(spec, shape, prec)
    model_size = an.params * prec.bytes_per_param
    # runtime memory = weights + KV cache + activations + resident overhead
    runtime = (model_size + an.memory.kv_cache + an.memory.activations
               + _RUNTIME_OVERHEAD)
    an.memory.weights = model_size          # single-device: no sharding
    per_op = per_operator_flops(spec, seq_len)
    lat = lat_mod.breakdown(an, hw, prec, per_op_flops=per_op)
    ai = lat_mod.arithmetic_intensity(an, prec)
    en = energy_mod.energy(an, hw, prec)
    tokens = batch if kind == "decode" else seq_len * batch
    return Report(
        model=spec.name, hardware=hw.name, precision=prec.name, seq_len=seq_len,
        params=an.params, flops_per_token=an.flops_per_token,
        model_size_bytes=model_size, memory_runtime_bytes=runtime,
        latency=lat, arithmetic_intensity=ai,
        energy_per_token_j=en.total / max(1, tokens), analysis=an)


def per_operator_flops(spec: ModelSpec, s_ctx: int) -> Dict[str, float]:
    """Paper §III-B fine-grained split: attention-projection, KV matmuls,
    MLP, layernorm, softmax — per token."""
    from repro.core import blocks
    d, q, kv = spec.d_model, spec.q_dim, spec.kv_dim
    n_attn = spec.num_attention_layers()
    out = {
        "attn_proj": n_attn * (2 * d * q + 4 * d * kv + 2 * q * d),
        "kv_matmul": n_attn * 4 * s_ctx * q,
        "softmax": n_attn * 7 * spec.num_heads * s_ctx,
        "layernorm": 2 * spec.num_layers * 5 * d,
        "lm_head": 2 * d * spec.padded_vocab,
    }
    mlp = 0.0
    for i, k in enumerate(spec.layer_kinds()):
        if not k.startswith("attn") and k != "conv":
            continue
        mlp += blocks.ffn_flops_per_token(spec, i)
    out["mlp"] = mlp
    return out


def sweep(specs, hardwares, precisions, seq_len: int = 2048):
    """Cartesian sweep — the loop behind paper Fig. 4 and Table II."""
    for spec in specs:
        for hw in hardwares:
            for prec in precisions:
                yield profile(spec, hw, prec, seq_len=seq_len)
