"""Static TPU-constraint checks for Pallas kernel configurations.

Mosaic enforces its limits at compile time on a TPU host with opaque
errors ("scoped vmem limit exceeded", bad layouts); this module checks the
same constraints from the kernel's *declared* block configuration — pure
arithmetic, runs anywhere, and turns tuning folklore (the packed flash
kernel's "cap backward score tiles at 256, 512-square overflows the 16MB
scoped-VMEM stack" — see ``ops/_pallas/flash_attention_packed.py``) into
enforced, explainable diagnostics.

Checked per :class:`KernelSpec`:
  P001  estimated VMEM footprint (block tiles + scratch + live score
        temporaries + in-kernel im2col tiles) vs the ~16MB
        per-core budget                                      [error]
  P002  tile alignment: last dim % 128, second-minor % dtype sublane
        (8 f32 / 16 bf16 / 32 int8)                          [warning]
  P003  grid/block divisibility: every blocked dim must divide [error]
  P004  a single score tile consuming over half the budget    [warning]

``enforce`` is the kernel-side hook: builds the spec, checks, and routes
through :func:`jaxpr_lint.emit` under ``FLAGS_static_analysis``. The
conv kernel family (``ops/_pallas/conv.py``) declares its im2col working
set (the nine VMEM-assembled tap tiles plus the f32 accumulator) via
:attr:`KernelSpec.im2col`, so the budget check covers the one footprint
a BlockSpec reading misses; its ``supports()`` routability test refuses
any config these checks reject (fallback to lax, never a Mosaic error).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ._jaxpr_utils import fmt_shape
from .jaxpr_lint import Diagnostic, ERROR, WARNING, emit

__all__ = ["VMEM_BUDGET", "KernelSpec", "BlockUse", "check_kernel_spec",
           "spec_for_flash_packed", "spec_for_flash", "spec_for_conv_matmul",
           "spec_for_conv3x3", "spec_for_paged_decode", "enforce",
           "report_fallback",
           "check_jaxpr_pallas"]

# Mosaic's scoped-VMEM stack per core (v4/v5 generations): ~16 MB.
VMEM_BUDGET = 16 * 1024 * 1024

# dtype itemsize -> minimum sublane count of a native tile (lane dim 128)
_SUBLANE = {4: 8, 2: 16, 1: 32}
_LANE = 128


@dataclass
class BlockUse:
    """One VMEM-resident buffer: a BlockSpec tile or a scratch shape."""
    shape: Tuple[int, ...]
    dtype: Any = np.float32
    label: str = ""

    def bytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= int(d)
        return n * np.dtype(self.dtype).itemsize


@dataclass
class KernelSpec:
    """Declared configuration of one pallas_call site."""
    name: str
    grid: Tuple[int, ...] = ()
    blocks: List[BlockUse] = field(default_factory=list)    # in + out tiles
    scratch: List[BlockUse] = field(default_factory=list)
    # (label, full_dim, block_dim) pairs that must divide
    dims: List[Tuple[str, int, int]] = field(default_factory=list)
    # flash-style kernels: (block_q, block_k, live_f32_temporaries) — the
    # [bq, bk] score/probability tiles Mosaic keeps on the scoped stack
    score_tile: Optional[Tuple[int, int, int]] = None
    # conv-style kernels: VMEM-assembled im2col tap tiles + accumulators
    # that never appear in any BlockSpec (live kernel temporaries)
    im2col: List[BlockUse] = field(default_factory=list)


def _vmem_estimate(spec: KernelSpec) -> Tuple[int, str]:
    tile_b = sum(b.bytes() for b in spec.blocks)
    scratch_b = sum(b.bytes() for b in spec.scratch)
    score_b = 0
    if spec.score_tile:
        bq, bk, live = spec.score_tile
        score_b = bq * bk * 4 * live
    im2col_b = sum(b.bytes() for b in spec.im2col)
    total = tile_b + scratch_b + score_b + im2col_b
    detail = (f"{tile_b / 2**20:.1f}MB tiles + "
              f"{scratch_b / 2**20:.1f}MB scratch + "
              f"{score_b / 2**20:.1f}MB live score temporaries")
    if spec.im2col:
        detail += f" + {im2col_b / 2**20:.1f}MB im2col tiles"
    return total, detail


def check_kernel_spec(spec: KernelSpec) -> List[Diagnostic]:
    diags: List[Diagnostic] = []
    total, detail = _vmem_estimate(spec)
    if total > VMEM_BUDGET:
        diags.append(Diagnostic(
            rule="P001", name="vmem-budget", severity=ERROR,
            message=(f"kernel '{spec.name}' needs ~{total / 2**20:.1f}MB "
                     f"VMEM ({detail}) — over the "
                     f"{VMEM_BUDGET // 2**20}MB scoped-VMEM budget; "
                     "Mosaic will fail or spill"),
            hint="shrink block_q/block_k (the packed flash backward caps "
                 "score tiles at 256) or stream over a larger grid"))
    for b in spec.blocks + spec.scratch + spec.im2col:
        if len(b.shape) < 2:
            continue
        minor = int(b.shape[-1])
        second = int(b.shape[-2])
        if minor < _LANE:
            # sub-lane-width accumulators (m/l columns, lse tiles) are a
            # deliberate narrow layout, not a mis-sized big tile
            continue
        sub = _SUBLANE.get(np.dtype(b.dtype).itemsize, 8)
        if minor % _LANE or (second % sub and second != 1):
            diags.append(Diagnostic(
                rule="P002", name="tile-alignment", severity=WARNING,
                message=(f"kernel '{spec.name}' block "
                         f"{b.label or fmt_shape(b.shape)} = "
                         f"{fmt_shape(b.shape)} ({np.dtype(b.dtype).name}) "
                         f"is not a multiple of the native "
                         f"({sub}, {_LANE}) tile — Mosaic pads every "
                         "load/store"),
                hint=f"pad the minor dims to ({sub}, {_LANE}) multiples"))
    for label, full, block in spec.dims:
        if block and int(full) % int(block):
            diags.append(Diagnostic(
                rule="P003", name="grid-divisibility", severity=ERROR,
                message=(f"kernel '{spec.name}': dim {label}={full} is not "
                         f"divisible by its block size {block} — partial "
                         "edge tiles are not configured"),
                hint="pick a dividing block size or pad the operand"))
    if spec.score_tile:
        bq, bk, live = spec.score_tile
        one_tile = bq * bk * 4
        if one_tile * max(live, 1) > VMEM_BUDGET // 2:
            diags.append(Diagnostic(
                rule="P004", name="score-tile-cap", severity=WARNING,
                message=(f"kernel '{spec.name}': {live} live [{bq}, {bk}] "
                         f"f32 score tiles = "
                         f"{one_tile * max(live, 1) / 2**20:.1f}MB — over "
                         "half the scoped-VMEM budget; leaves no headroom "
                         "for operand tiles"),
                hint="cap the streamed-axis block at 256 for backward "
                     "kernels"))
    return diags


# ---------------------------------------------------------------------------
# Spec builders for the repo's own kernels
# ---------------------------------------------------------------------------

def spec_for_flash_packed(seq_q: int, seq_k: int, packed_d: int,
                          block_q: int, block_k: int, g_pack: int,
                          dtype=np.float32, bwd: bool = False) -> KernelSpec:
    """Spec for ops/_pallas/flash_attention_packed.py at one config.

    Forward keeps ~2 live [bq, bk] f32 temporaries per head iteration
    (scores + probabilities); backward ~5 (s, p, dp, ds and a mask/keep
    factor) — the measured reason 512-square backward tiles overflow.
    """
    bq, bk = min(block_q, seq_q), min(block_k, seq_k)
    dt = np.dtype(dtype)
    blocks = [BlockUse((bq, packed_d), dt, "q"),
              BlockUse((bk, packed_d), dt, "k"),
              BlockUse((bk, packed_d), dt, "v"),
              BlockUse((bq, packed_d), dt, "o")]
    scratch = [BlockUse((bq, g_pack), np.float32, "m"),
               BlockUse((bq, g_pack), np.float32, "l"),
               BlockUse((bq, packed_d), np.float32, "acc")]
    live = 2
    if bwd:
        blocks += [BlockUse((bq, packed_d), dt, "do"),
                   BlockUse((bk, packed_d), dt, "dk"),
                   BlockUse((bk, packed_d), dt, "dv")]
        scratch = [BlockUse((bk, packed_d), np.float32, "dk_acc"),
                   BlockUse((bk, packed_d), np.float32, "dv_acc")]
        live = 5
    return KernelSpec(
        name="flash_attention_packed" + ("_bwd" if bwd else ""),
        grid=(max(1, seq_q // bq), max(1, seq_k // bk)),
        blocks=blocks, scratch=scratch,
        dims=[("seq_q", seq_q, bq), ("seq_k", seq_k, bk)],
        score_tile=(bq, bk, live))


def spec_for_conv_matmul(m: int, cin: int, cout: int, block_m: int,
                         dtype=np.float32, wgrad: bool = False) -> KernelSpec:
    """Spec for the 1x1-as-matmul conv kernels of ``ops/_pallas/conv.py``
    (forward/dgrad share a kernel; ``wgrad=True`` models the a^T@dy
    accumulator, whose f32 [Cin, Cout] scratch is the footprint risk)."""
    dt = np.dtype(dtype)
    bm = min(block_m, m)
    blocks = [BlockUse((bm, cin), dt, "x"),
              BlockUse((1, cin), np.float32, "scale"),
              BlockUse((1, cin), np.float32, "shift")]
    if wgrad:
        blocks += [BlockUse((bm, cout), dt, "dy"),
                   BlockUse((cin, cout), np.float32, "dw")]
        scratch = [BlockUse((cin, cout), np.float32, "dw_acc")]
    else:
        blocks += [BlockUse((cin, cout), dt, "w"),
                   BlockUse((bm, cout), dt, "y"),
                   BlockUse((1, cout), np.float32, "s"),
                   BlockUse((1, cout), np.float32, "ss")]
        scratch = [BlockUse((1, cout), np.float32, "s_acc"),
                   BlockUse((1, cout), np.float32, "ss_acc")]
    # the f32 MXU accumulator tile is live alongside the operand tiles
    im2col = [BlockUse((bm, cout) if not wgrad else (cin, cout),
                       np.float32, "acc")]
    return KernelSpec(
        name="pallas_conv1x1" + ("_wgrad" if wgrad else ""),
        grid=(1, max(1, m // bm)),
        blocks=blocks, scratch=scratch, im2col=im2col,
        dims=[("m", m, bm)])


def spec_for_conv3x3(n: int, h: int, w: int, c: int, cout: int,
                     block_h: int, stride: int, dtype=np.float32,
                     pad: int = 1, wgrad: bool = False) -> KernelSpec:
    """Spec for the NHWC 3x3 conv kernels at one block configuration.

    The padded image rides VMEM whole per batch index; each grid step
    assembles nine [block_h*Wo, C] im2col tap tiles in VMEM next to the
    f32 [block_h*Wo, Cout] accumulator — the footprint a BlockSpec
    reading misses, declared via ``im2col``."""
    dt = np.dtype(dtype)
    hp, wp = h + 2 * pad, w + 2 * pad
    ho = (hp - 3) // stride + 1
    wo = (wp - 3) // stride + 1
    bh = min(block_h, ho)
    blocks = [BlockUse((hp, wp, c), dt, "image"),
              BlockUse((9, c, cout), dt, "taps"),
              BlockUse((1, c), np.float32, "scale"),
              BlockUse((1, c), np.float32, "shift")]
    if wgrad:
        blocks += [BlockUse((bh, wo, cout), dt, "dy"),
                   BlockUse((9, c, cout), np.float32, "dw")]
        scratch = [BlockUse((9, c, cout), np.float32, "dw_acc")]
        acc = BlockUse((c, cout), np.float32, "tap_acc")
    else:
        blocks += [BlockUse((bh, wo, cout), dt, "y"),
                   BlockUse((1, cout), np.float32, "s"),
                   BlockUse((1, cout), np.float32, "ss")]
        scratch = [BlockUse((1, cout), np.float32, "s_acc"),
                   BlockUse((1, cout), np.float32, "ss_acc")]
        acc = BlockUse((bh * wo, cout), np.float32, "acc")
    im2col = [BlockUse((bh * wo, c), dt, "im2col tap"), acc]
    return KernelSpec(
        name="pallas_conv3x3" + ("_wgrad" if wgrad else ""),
        grid=(n, max(1, ho // bh)),
        blocks=blocks, scratch=scratch, im2col=im2col,
        dims=[("h_out", ho, bh)])


def spec_for_flash(seq_q: int, seq_k: int, head_d: int, block_q: int,
                   block_k: int, dtype=np.float32,
                   bwd: bool = False) -> KernelSpec:
    """Spec for the plain per-head flash kernel (g_pack == 1)."""
    spec = spec_for_flash_packed(seq_q, seq_k, head_d, block_q, block_k,
                                 1, dtype, bwd)
    spec.name = "flash_attention" + ("_bwd" if bwd else "")
    return spec


def spec_for_paged_decode(batch: int, table_width: int, block_size: int,
                          heads: int, kv_heads: int, head_d: int,
                          pages_per_step: Optional[int] = None,
                          dtype=np.float32) -> KernelSpec:
    """Spec for the paged single-query decode kernel
    (``ops/_pallas/paged_attention.py``): q and the output whole in VMEM,
    two slots of ``pages_per_step`` pages each for K and for V (a page read
    as ``[block_size * kv_heads, head_d]``), and the ``[heads, tokens *
    kv_heads]`` float32 score tile with its probabilities and masks."""
    if pages_per_step is None:
        from ..ops._pallas.paged_attention import PAGES_PER_STEP
        pages_per_step = PAGES_PER_STEP
    pages = max(1, min(pages_per_step, table_width))
    step_rows = pages * block_size * kv_heads
    qo = BlockUse((batch, heads, head_d), dtype, "q/out")
    slot = BlockUse((2, step_rows, head_d), dtype, "page slots")
    return KernelSpec(
        name="paged_single_query_attention", grid=(1,),
        blocks=[qo, qo], scratch=[slot, slot],
        score_tile=(heads, step_rows, 4))


def enforce(spec: KernelSpec, where: str = "") -> List[Diagnostic]:
    """Kernel-side hook: check and route per FLAGS_static_analysis.
    No-op (and near-zero cost) when the flag is off."""
    from .jaxpr_lint import analysis_mode
    if analysis_mode() == "off":
        return []
    diags = check_kernel_spec(spec)
    return emit(diags, where=where or spec.name)


_FALLBACKS_REPORTED: set = set()


def report_fallback(kernel: str, shape: str, reason: str) -> None:
    """P005: on a TPU, a Pallas kernel was asked for (its flag is on) and
    the call takes the XLA path instead. Reported once per (kernel,
    shape), whatever ``FLAGS_static_analysis`` says — a fallback that is
    not announced hides an unused chip path."""
    if (kernel, shape) in _FALLBACKS_REPORTED:
        return
    _FALLBACKS_REPORTED.add((kernel, shape))
    d = Diagnostic(
        rule="P005", name="kernel-fallback", severity=WARNING,
        message=f"{kernel} does not take its Pallas kernel for {shape}: "
                f"{reason} — this call runs the XLA path",
        where=kernel,
        hint="pad/reshape to a supported shape, or switch the kernel's "
             "flag off to make the XLA path the declared one")
    emit([d], where=kernel, mode="warn")


# ---------------------------------------------------------------------------
# jaxpr-side discovery (best effort across jax versions)
# ---------------------------------------------------------------------------

def check_jaxpr_pallas(closed_jaxpr) -> List[Diagnostic]:
    """Find pallas_call equations in a traced program and check what their
    params expose (block shapes via the grid mapping when available)."""
    from ._jaxpr_utils import inner_jaxprs
    diags: List[Diagnostic] = []

    def specs_of(eqn) -> Optional[KernelSpec]:
        try:
            gm = eqn.params.get("grid_mapping")
            name = eqn.params.get("name") or "pallas_call"
            blocks = []
            if gm is not None:
                for bm in getattr(gm, "block_mappings", ()) or ():
                    shape = tuple(int(d) for d in
                                  getattr(bm, "block_shape", ()) or ()
                                  if isinstance(d, (int, np.integer)))
                    if shape:
                        blocks.append(BlockUse(shape, np.float32))
                grid = tuple(int(g) for g in getattr(gm, "grid", ()) or ()
                             if isinstance(g, (int, np.integer)))
            else:
                grid = ()
            return KernelSpec(name=str(name), grid=grid, blocks=blocks)
        except Exception:
            return None

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                spec = specs_of(eqn)
                if spec is not None:
                    diags.extend(check_kernel_spec(spec))
            for _, inner in inner_jaxprs(eqn):
                walk(inner.jaxpr)

    walk(closed_jaxpr.jaxpr)
    return diags
