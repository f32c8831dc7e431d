"""Pallas kernel budget verifier: block/VMEM/accumulator mistakes fail
at lint time, not on a burned 900-second TPU bench round.

Every Pallas kernel family the repo ships (the TPP micro-kernel registry
``ops/tpp.py``, flash attention ``ops/flash_attention.py``, the NMS sweep
``ops/nms_pallas.py``, the decode step's in-place KV-cache store
``ops/kv_store.py``, its read of the live cache tiles, with and without
that store folded in, ``ops/decode_attention.py`` and the latent family's
``ops/latent_decode_attention.py``) exposes an ``audit_manifest()``: a list of
declarative entries describing what each kernel compiles to at its
representative shapes — grid dims with their block edges, every
VMEM-resident buffer's block shape and dtype, scratch allocations, and
the matmul accumulator dtype. The manifest is pure arithmetic (no pallas
import, no tracing), so the whole audit runs in milliseconds.

Checks per entry (TPU facts per /opt/skills/guides/pallas_guide.md):

- ``kernel-grid-indivisible`` (error): a grid dim's block edge must
  divide the dim exactly — a ragged tail block reads out of bounds (or
  silently pads, depending on lowering: both are wrong answers);
- ``kernel-block-misaligned`` (warning/info): the minor-most block dim
  should be a multiple of the 128-lane register width (info: the block
  pads to a full lane tile, wasting lanes) and the second-minor a
  multiple of the dtype's sublane tile — 8 for f32, 16 for bf16, 32 for
  int8/fp8 (warning: every access pays a re-layout);
- ``kernel-vmem-over-budget`` (error): streamed blocks are
  double-buffered by the Pallas pipeline (x2), scratch is resident (x1);
  the static total must fit the per-core VMEM budget (16 MiB) — the
  finding carries the per-buffer breakdown, largest first;
- ``kernel-low-precision-accumulator`` (error): a matmul-class kernel
  consuming bf16/int8/fp8 inputs must accumulate in float32 (the MXU
  accumulates f32; an int8/bf16 accumulator silently saturates/rounds).

CLI: ``python tools/contract_audit.py --pallas`` (and
``graph_lint.py --contracts``); tier-1: tests/test_sharding_gate.py.
"""
from .registry import Finding

RULES = {
    "kernel-grid-indivisible": "error",
    "kernel-block-misaligned": "warning",
    "kernel-vmem-over-budget": "error",
    "kernel-low-precision-accumulator": "error",
}

#: per-core VMEM (v4/v5 class cores; pallas_guide.md "~16 MB/core")
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
LANE = 128
#: min sublane tile (second-minor dim) per dtype
SUBLANE = {"float32": 8, "int32": 8, "uint32": 8,
           "bfloat16": 16, "float16": 16,
           "int8": 32, "uint8": 32, "float8_e4m3fn": 32,
           "float8_e5m2": 32}
_ITEMSIZE = {"float32": 4, "int32": 4, "uint32": 4,
             "bfloat16": 2, "float16": 2,
             "int8": 1, "uint8": 1, "float8_e4m3fn": 1,
             "float8_e5m2": 1, "bool": 1}
LOW_PRECISION = ("bfloat16", "float16", "int8", "uint8",
                 "float8_e4m3fn", "float8_e5m2")


def _itemsize(dtype):
    return _ITEMSIZE.get(str(dtype), 4)


def buffer_bytes(buf):
    """Static VMEM bytes of one manifest buffer, double-buffering
    included (streamed blocks hold block N and block N+1 in flight)."""
    n = 1
    for d in buf.get("block", ()):
        n *= int(d)
    return n * _itemsize(buf.get("dtype", "float32")) * \
        (2 if buf.get("stream", True) else 1)


def vmem_breakdown(entry):
    """[(name, bytes)] largest first + the total — the per-buffer
    breakdown an over-budget finding names."""
    rows = [(b.get("name", f"buf{i}"), buffer_bytes(b))
            for i, b in enumerate(entry.get("buffers", ()))]
    rows.sort(key=lambda r: (-r[1], r[0]))
    return rows, sum(b for _, b in rows)


def audit_entry(entry, budget=VMEM_BUDGET_BYTES):
    """Findings for one manifest entry."""
    out = []
    kern = entry.get("kernel", "?")
    where = kern

    for dim, (size, block) in sorted(entry.get("grid", {}).items()):
        if block in (None, 0) or size in (None, 0):
            continue
        if int(size) % int(block):
            out.append(Finding(
                "kernel-grid-indivisible", "error",
                f"{kern}: grid dim '{dim}' = {size} is not divisible by "
                f"its block edge {block} — the last grid step reads a "
                f"ragged {size % block}-wide tail", where=where))

    lane_pads, sublane_bad = [], []
    for buf in entry.get("buffers", ()):
        block = tuple(int(d) for d in buf.get("block", ()))
        if len(block) < 2:
            continue
        name = buf.get("name", "?")
        dt = str(buf.get("dtype", "float32"))
        minor, second = block[-1], block[-2]
        if minor > 1 and minor % LANE:
            lane_pads.append(f"{name}[..{minor}]")
        sub = SUBLANE.get(dt, 8)
        if second > 1 and second % sub:
            sublane_bad.append(f"{name}[{second}x{minor} {dt}, "
                               f"min tile ({sub}, {LANE})]")
    if lane_pads:
        out.append(Finding(
            "kernel-block-misaligned", "info",
            f"{kern}: {len(lane_pads)} buffer(s) with a lane dim below "
            f"the {LANE}-lane register width ({', '.join(lane_pads[:5])})"
            " — each block pads to a full lane tile (wasted lanes)",
            where=where))
    if sublane_bad:
        out.append(Finding(
            "kernel-block-misaligned", "warning",
            f"{kern}: sublane dim not a multiple of the dtype min tile "
            f"({', '.join(sublane_bad[:5])}) — every access pays a "
            "re-layout", where=where))

    rows, total = vmem_breakdown(entry)
    if total > budget:
        detail = ", ".join(f"{n}={b / 1024:.0f}KiB" for n, b in rows[:6])
        out.append(Finding(
            "kernel-vmem-over-budget", "error",
            f"{kern}: static VMEM {total / (1 << 20):.1f} MiB exceeds "
            f"the {budget / (1 << 20):.0f} MiB per-core budget "
            f"(streamed blocks double-buffered; breakdown: {detail}) — "
            "shrink the block edges or move a buffer to grid streaming",
            where=where))

    if entry.get("matmul"):
        in_dt = str(entry.get("in_dtype", "float32"))
        acc_dt = str(entry.get("acc_dtype", ""))
        if in_dt in LOW_PRECISION and acc_dt != "float32":
            out.append(Finding(
                "kernel-low-precision-accumulator", "error",
                f"{kern}: {in_dt} matmul accumulates in "
                f"{acc_dt or 'the input dtype'} — partial products "
                "saturate/round silently; accumulate in a float32 VMEM "
                "scratch (preferred_element_type=float32)", where=where))
    return out


def audit_tile(name, block, dtype="float32", budget=VMEM_BUDGET_BYTES,
               stream=True):
    """Findings for one synthetic streamed buffer tile — the planner's
    per-stage activation working set (analysis/plan_search.py) priced
    with the SAME rules as registered kernels: streamed blocks are
    double-buffered, the budget is the 16 MiB per-core VMEM. Alignment
    findings ride along at their usual severities; only the budget rule
    is an error."""
    entry = {"kernel": str(name), "matmul": False, "grid": {},
             "buffers": [{"name": "tile", "block": tuple(block),
                          "dtype": str(dtype), "stream": bool(stream)}]}
    return audit_entry(entry, budget=budget)


def collect_manifest():
    """Every registered kernel family's manifest entries. Imports the
    ops modules (jax import cost only — nothing compiles or runs)."""
    from ..ops import decode_attention, flash_attention, kv_store, \
        latent_decode_attention, nms_pallas, tpp

    entries = []
    for mod in (tpp, flash_attention, nms_pallas, kv_store,
                decode_attention, latent_decode_attention):
        entries.extend(mod.audit_manifest())
    return entries


def audit_package(budget=VMEM_BUDGET_BYTES):
    """The full kernel audit over every registered family."""
    out = []
    for entry in collect_manifest():
        out.extend(audit_entry(entry, budget=budget))
    return out
