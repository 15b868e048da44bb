"""A deployment's latency and energy profile, from its configuration file.

``profile(cfg)`` returns two float64 arrays indexed by batch size a =
0..b_max (entry 0 is 0): the mean service time l(a) in ms and the energy
zeta(a) in mJ of one batch.  Both the program and the plain reference
get these same numbers; the program receives them as table profiles.
"""
from __future__ import annotations

import numpy as np


def _affine(p, b):
    return p["slope"] * b + p["intercept"]


def param_count(model: dict) -> float:
    """Weights of a dense decoder with gated MLP, from its published widths."""
    d = model["hidden_size"]
    heads, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                     model["head_dim"])
    attn = d * (heads + 2 * kv) * hd + heads * hd * d
    mlp = 3 * d * model["intermediate_size"]
    emb = model["vocab_size"] * d * (1 if model["tie_word_embeddings"] else 2)
    return float(emb + model["num_hidden_layers"] * (attn + mlp))


def _decode_roofline(cfg, b):
    prof, model = cfg["profile"], cfg["model"]
    chip = prof["chip"]
    n = param_count(model)
    width = prof["bytes_per_value"]
    chips = prof["chips_per_replica"]
    tokens = prof["tokens_per_service"]
    kv = (2 * model["num_hidden_layers"] * model["num_key_value_heads"]
          * model["head_dim"] * prof["context_len"] * width)
    compute = b * 2.0 * n / (chip["peak_flops"] * chips)
    memory = (n * width + b * kv) / (chip["hbm_bytes_per_s"] * chips)
    lat = tokens * np.maximum(compute, memory) * 1e3
    e_flop = (chip["p_peak_w"] - chip["p_static_w"]) / chip["peak_flops"]
    energy = chip["p_static_w"] * lat + e_flop * tokens * 2.0 * n * b * 1e3
    return lat, energy


def profile(cfg: dict):
    b = np.arange(1, cfg["b_max"] + 1, dtype=np.float64)
    kind = cfg["profile"]["kind"]
    if kind == "affine":
        lat = _affine(cfg["profile"]["latency_ms"], b)
        energy = _affine(cfg["profile"]["energy_mj"], b)
    elif kind == "decode_roofline":
        lat, energy = _decode_roofline(cfg, b)
    else:
        raise ValueError(f"unknown profile kind {kind!r}")
    if cfg["service"] != "det":
        raise ValueError("the reference models deterministic service only")
    return np.concatenate([[0.0], lat]), np.concatenate([[0.0], energy])


def arrival_rate(cfg: dict, rho: float) -> float:
    """Per-server Poisson rate at load rho: rho b_max / l(b_max)."""
    lat, _ = profile(cfg)
    return rho * cfg["b_max"] / lat[cfg["b_max"]]


def program_spec(cfg: dict, rho: float, w2: float):
    """The program's SMDPSpec of one operating point."""
    from repro.core import ServiceModel, SMDPSpec, TableProfile

    lat, energy = profile(cfg)
    return SMDPSpec(
        lam=arrival_rate(cfg, rho),
        service=ServiceModel(latency=TableProfile(tuple(lat[1:])), family="det"),
        energy=TableProfile(tuple(energy[1:])),
        b_min=cfg["b_min"], b_max=cfg["b_max"], w1=cfg["w1"], w2=float(w2),
        s_max=cfg["s_max"], c_o=cfg["c_o"],
    )
