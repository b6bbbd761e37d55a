"""Three-term roofline model from a compiled SPMD artifact (§Roofline).

    compute    = HLO_FLOPs(per-device)      / peak_FLOP/s per chip
    memory     = HLO_bytes(per-device)      / HBM bytes/s per chip
    collective = collective_bytes(per-dev)  / ICI bytes/s per link

cost_analysis() reports per-device numbers for SPMD programs (verified
empirically: a (32,128)x(128,256) matmul on 8 devices reports 1/8 of the
global FLOPs). Collective bytes are NOT in cost_analysis — they are parsed
from the compiled HLO text by summing the result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

from repro.launch.mesh import TPU_V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-gather.3 = bf16[16,2048]{1,0} all-gather(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[0-9,]*\][^)\s]*\s*,?\s*)+)\)?\s*"
    r"(" + "|".join(_COLLECTIVES) + r")\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind byte totals (result shapes, per-device)."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        total = sum(shape_bytes(dt, dims)
                    for dt, dims in _SHAPE_RE.findall(shapes))
        out[kind] += total
        counts[kind] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-device HLO FLOPs
    hbm_bytes: float             # per-device bytes accessed
    coll_bytes: float            # per-device collective bytes
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: Optional[float] = None   # 6·N·D (global)
    useful_ratio: Optional[float] = None  # MODEL / (HLO · chips)
    coll_detail: Optional[dict] = None

    def as_dict(self):
        return dataclasses.asdict(self)


def analyze(compiled, *, chips: int, model_flops: Optional[float] = None,
            hw: dict = TPU_V5E) -> Roofline:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0))
    coll = collective_bytes(compiled.as_text())
    t_c = flops / hw["peak_bf16_flops"]
    t_m = hbm / hw["hbm_bw"]
    t_x = coll["total"] / hw["ici_bw"]
    bottleneck = max((("compute", t_c), ("memory", t_m),
                      ("collective", t_x)), key=lambda kv: kv[1])[0]
    useful = (model_flops / (flops * chips)
              if model_flops and flops else None)
    return Roofline(flops=flops, hbm_bytes=hbm, coll_bytes=coll["total"],
                    compute_s=t_c, memory_s=t_m, collective_s=t_x,
                    bottleneck=bottleneck, model_flops=model_flops,
                    useful_ratio=useful, coll_detail=coll)


def count_params(tree) -> int:
    import jax
    return sum(int(l.size) for l in jax.tree.leaves(tree))


def model_flops_train(cfg, abstract_params, tokens: int) -> float:
    """6·N·D with MoE activation discounting (6·N_active·D)."""
    import jax
    total = active = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            abstract_params)[0]:
        keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        n = int(leaf.size)
        total += n
        if "moe/w_" in keys and cfg.n_experts:
            active += n * cfg.experts_per_tok / cfg.n_experts
        elif "embed/tok" in keys or "lm_head" in keys:
            # embedding gather is not a matmul; the LM head is — count the
            # head, skip the table (standard 6ND convention)
            active += n if "lm_head" in keys else 0
        else:
            active += n
    return 6.0 * active * tokens


def model_flops_decode(cfg, abstract_params, tokens: int) -> float:
    """2·N_active per generated token (forward only)."""
    return model_flops_train(cfg, abstract_params, tokens) / 3.0
