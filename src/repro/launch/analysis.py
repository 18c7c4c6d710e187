"""Roofline-term extraction from compiled dry-run artifacts.

Methodology (EXPERIMENTS.md §Roofline):

XLA's HloCostAnalysis visits a `while` body ONCE — a scanned-depth model
reports ~one block of FLOPs regardless of trip count (verified empirically;
see tests/test_analysis.py). We therefore assemble per-device totals from
compiled artifacts as:

  1. full scanned compile          → memory_analysis (fits-HBM proof),
                                     compile feasibility (the dry-run gate)
  2. unrolled 1-block + 2-block    → per-block cost by differencing:
     analysis compiles                inside = C(2) − C(1);
                                      outside = C(1) − inside (clamped ≥ 0);
                                      total = outside + n_blocks · inside
  3. analytic corrections          → interiors of *time* loops, which stay
     (flagged per cell)              `while`s even in the unrolled-block
                                     lowering: chunked-attention streaming,
                                     Mamba/RWKV recurrence flops/bytes.

Collective bytes are parsed from the unrolled compiles' optimized HLO
(result-type bytes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute) and scaled by the same differencing — block-level
collectives (FSDP gathers, row-parallel psums) all live at block scope, and
the time-loop interiors are collective-free by construction (sharding rules
keep recurrences local), so no correction term is needed for comms.

Hardware model (TPU v5e-class, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.analysis.auditor import (HLO_COLLECTIVES, HLO_SHAPE_RE,
                                    hlo_collective_ops)
from repro.models.config import ModelConfig

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {'f64': 8, 'f32': 4, 'f16': 2, 'bf16': 2, 's64': 8, 'u64': 8,
                's32': 4, 'u32': 4, 's16': 2, 'u16': 2, 's8': 1, 'u8': 1,
                'pred': 1, 'f8e4m3fn': 1, 'f8e5m2': 1}


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in HLO_SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(','):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, Any]:
    """Per-device bytes by collective kind from optimized HLO text.
    `-start` variants counted, `-done` skipped (same transfer)."""
    out = {k: 0 for k in HLO_COLLECTIVES}
    counts = {k: 0 for k in HLO_COLLECTIVES}
    for kind, type_str, _ in hlo_collective_ops(hlo_text):
        out[kind] += _type_bytes(type_str)
        counts[kind] += 1
    return {'bytes': out, 'counts': counts,
            'total_bytes': sum(out.values())}


# --------------------------------------------------------- analytic interiors
def _attention_interior(cfg: ModelConfig, batch_local: int, seq: int,
                        train: bool, heads_local: int) -> dict:
    """Chunked-attention streaming cost per attention layer (per device).
    Dense (non-causal-skipping) baseline — matches the executed code."""
    hd = cfg.head_dim
    flops = 4.0 * batch_local * seq * seq * heads_local * hd   # QKᵀ + PV
    nq = max(seq // cfg.attn_chunk, 1)
    kv_bytes = 2 * batch_local * seq * heads_local * hd * 2    # K+V bf16
    bytes_ = nq * kv_bytes + 2 * batch_local * seq * heads_local * hd * 2
    if train:      # backward ≈ 2× forward flops + remat recompute ≈ 1×
        flops *= 3.5
        bytes_ *= 3.0
    return {'flops': flops, 'bytes': bytes_}


def _ssm_interior(cfg: ModelConfig, batch_local: int, seq: int,
                  train: bool, di_local: int) -> dict:
    ds = cfg.d_state
    flops = 6.0 * batch_local * seq * di_local * ds
    bytes_ = 3.0 * batch_local * seq * di_local * ds * 4       # f32 states
    if train:
        flops *= 3.5
        bytes_ *= 3.0
    return {'flops': flops, 'bytes': bytes_}


def _rwkv_interior(cfg: ModelConfig, batch_local: int, seq: int,
                   train: bool, heads_local: int) -> dict:
    flops = 7.0 * batch_local * seq * heads_local * 64 * 64
    bytes_ = 2.0 * batch_local * seq * heads_local * 64 * 64 * 4
    if train:
        flops *= 3.5
        bytes_ *= 3.0
    return {'flops': flops, 'bytes': bytes_}


def interior_corrections(cfg: ModelConfig, mesh, kind: str,
                         global_batch: int, seq: int) -> dict:
    """Per-device analytic cost of while-loop interiors (see module doc)."""
    from repro.distributed.sharding import batch_axes
    n_b = 1
    for a in batch_axes(mesh):
        n_b *= mesh.shape[a]
    b_local = max(global_batch // n_b, 1) if global_batch % n_b == 0 else global_batch
    m = mesh.shape['model'] if 'model' in mesh.axis_names else 1
    train = kind == 'train'

    flops = 0.0
    bytes_ = 0.0
    if kind == 'decode':     # no time loops at decode; nothing to correct
        return {'flops': 0.0, 'bytes': 0.0}
    for (mixer, _f) in cfg.layer_kinds():
        n_such = cfg.n_layers // cfg.block_period
        if mixer == 'attn':
            # mirrors _project_qkv: TP head-padding makes heads shard even
            # when H % m != 0 (padded to the next multiple of m)
            h_pad = (cfg.n_heads + m - 1) // m * m
            h_local = h_pad // m
            if seq > cfg.attn_chunk:
                c = _attention_interior(cfg, b_local, seq, train, h_local)
                flops += c['flops'] * n_such
                bytes_ += c['bytes'] * n_such
        elif mixer == 'mamba':
            di_local = cfg.d_inner // m if cfg.d_inner % m == 0 else cfg.d_inner
            c = _ssm_interior(cfg, b_local, seq, train, di_local)
            flops += c['flops'] * n_such
            bytes_ += c['bytes'] * n_such
        else:
            H = cfg.d_model // 64
            h_local = H // m if H % m == 0 else H
            c = _rwkv_interior(cfg, b_local, seq, train, h_local)
            flops += c['flops'] * n_such
            bytes_ += c['bytes'] * n_such
    if cfg.is_encdec and kind in ('train', 'prefill') and seq > cfg.attn_chunk:
        h_local = ((cfg.n_heads + m - 1) // m * m) // m
        c = _attention_interior(cfg, b_local, seq, train, h_local)
        flops += c['flops'] * cfg.n_enc_layers
        bytes_ += c['bytes'] * cfg.n_enc_layers
    return {'flops': flops, 'bytes': bytes_}


# ----------------------------------------------------------------- MODEL_FLOPS
def model_flops(cfg: ModelConfig, kind: str, global_batch: int,
                seq: int) -> float:
    """Global 6·N·D (train) / 2·N·D (serve) with N = active params."""
    n_active = cfg.param_count(active_only=True)
    if kind == 'train':
        return 6.0 * n_active * global_batch * seq
    if kind == 'prefill':
        return 2.0 * n_active * global_batch * seq
    if kind == 'decode':
        return 2.0 * n_active * global_batch        # one token per sequence
    if kind == 'hypergrad':
        # k+1 HVPs (~2× fwd+bwd each) + 1 grad + 1 vjp ≈ (4k + 10)·N·D-ish;
        # report the k=8 configuration used by build_hypergrad_step
        return (4 * 8 + 10) * n_active * global_batch * seq
    raise ValueError(kind)


# ------------------------------------------------------------------ assembly
def _cost(compiled) -> dict:
    ca = compiled.cost_analysis()
    return {'flops': float(ca.get('flops', 0.0)),
            'bytes': float(ca.get('bytes accessed', 0.0))}


@dataclasses.dataclass
class CellAnalysis:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    flops_per_chip: float
    bytes_per_chip: float
    coll_bytes_per_chip: float
    coll_detail: dict
    correction: dict
    model_flops_global: float
    memory: dict
    compile_ok: bool
    error: str = ''

    def terms(self) -> dict:
        t_c = self.flops_per_chip / PEAK_FLOPS
        t_m = self.bytes_per_chip / HBM_BW
        t_x = self.coll_bytes_per_chip / ICI_BW
        dominant = max((t_c, 'compute'), (t_m, 'memory'),
                       (t_x, 'collective'))[1]
        useful = self.model_flops_global / max(self.n_chips, 1)
        return {'compute_s': t_c, 'memory_s': t_m, 'collective_s': t_x,
                'dominant': dominant,
                'bound_s': max(t_c, t_m, t_x),
                'roofline_fraction': (t_c / max(t_c, t_m, t_x)
                                      if max(t_c, t_m, t_x) > 0 else 0.0),
                'useful_flop_ratio': (useful / self.flops_per_chip
                                      if self.flops_per_chip else 0.0)}


def assemble(arch: str, shape: str, mesh_name: str, n_chips: int,
             c1: dict, c2: dict, n_blocks: int, coll1: dict, coll2: dict,
             corr: dict, mflops: float, memory: dict) -> CellAnalysis:
    """Differencing: inside = C2 − C1; outside = max(C1 − inside, 0)."""
    def diff(a, b):
        inside = max(b - a, 0.0)
        outside = max(a - inside, 0.0)
        return outside + n_blocks * inside

    flops = diff(c1['flops'], c2['flops']) + corr['flops']
    bytes_ = diff(c1['bytes'], c2['bytes']) + corr['bytes']
    coll = diff(float(coll1['total_bytes']), float(coll2['total_bytes']))
    detail = {k: diff(float(coll1['bytes'][k]), float(coll2['bytes'][k]))
              for k in HLO_COLLECTIVES}
    return CellAnalysis(
        arch=arch, shape=shape, mesh=mesh_name, n_chips=n_chips,
        flops_per_chip=flops, bytes_per_chip=bytes_,
        coll_bytes_per_chip=coll, coll_detail=detail, correction=corr,
        model_flops_global=mflops, memory=memory, compile_ok=True)
