"""Per-op HBM byte attribution from compiled (post-fusion) HLO text.

XLA's ``cost_analysis()["bytes accessed"]`` is one opaque number; this
module decomposes it so a bytes/image regression names the op category
that moved. The model is the same one XLA's cost analysis uses: every
top-level (non-fused) instruction in the optimized module reads its
operands from HBM and writes its output to HBM — instructions INSIDE a
fusion stay on-chip and cost nothing. Parsing the post-optimization
text (``compiled.as_text()``) means the counts reflect what the
compiler actually scheduled, remat and epilogue fusion included; the
per-device SPMD module is what prints, so counts are per chip, like
``cost_analysis``.

Categories (the byte-amplification suspects of the HBM-bound
MobileNetV2 step):

- ``conv_fwd`` / ``conv_bwd``  — convolutions (and conv-rooted
  fusions); bwd = ops under a ``transpose(...)`` autodiff scope.
- ``matmul``     — dot/dot-rooted fusions (the classifier head).
- ``bn``         — ops in a ``/bn/`` module scope: batch-stat
  reductions + the normalize/scale/shift/clamp epilogue regions.
- ``optimizer``  — the ``tpunet_optimizer`` / ``tpunet_ema`` named
  scopes (Adam moments, EMA).
- ``augment``    — the ``tpunet_augment`` named scope: the on-device
  input pipeline (resize/crop/rotate/jitter), a measured ~20%% of the
  round-4 step — kept distinct from model fwd work.
- ``copy_pad``   — layout traffic: copies, pads, transposes, slices,
  concats, converts at top level (or fusions rooted there).
- ``reduce``     — non-BN reductions (pool, loss, metrics).
- ``collective`` — cross-chip all-reduce/gather/permute traffic.
- ``elementwise``— everything else (augment chains, losses, adds).

``phase_of`` / ``is_backward`` classify framework op names by training
phase; scripts/obs_report.py reuses them for device-TIME attribution
from profiler traces, so the bytes and time tables split the step the
same way.

Known approximations (documented, stable across runs, so the >5%%
regression gate is still meaningful): ``while``/``conditional`` bodies
are counted once (the bench train step is straight-line at
grad_accum=1); CPU-backend ``call`` thunks are traversed into.
"""

from __future__ import annotations

import re
from typing import Dict, Iterator, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "fp8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# Produce/consume no HBM traffic of their own (aliases, metadata ops).
_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "domain", "opt-barrier",
}

# Traverse instead of count: their cost is the instructions they run.
_CALL_OPS = {"call", "while", "conditional", "async-start"}

_SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]{0,15})\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPERAND_RE = re.compile(r"%?([\w.\-]+)")
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_CALLED_RE = re.compile(
    r"(?:to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")

_COPY_ROOTS = {
    "copy", "pad", "transpose", "slice", "dynamic-slice", "dynamic_slice",
    "dynamic-update-slice", "dynamic_update_slice", "concatenate",
    "reshape", "convert", "gather", "scatter", "squeeze", "broadcast",
    "broadcast_in_dim", "rev", "copy-start",
}
_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "psum", "all_gather",
    "all_to_all", "ppermute",
}

CATEGORIES = ("conv_fwd", "conv_bwd", "matmul", "bn", "augment",
              "optimizer", "copy_pad", "reduce", "collective",
              "elementwise")

# ---------------------------------------------------------------------------
# Named-scope marker table — THE contract between the kernels in
# tpunet/ops/ and byte/phase attribution. Each custom_vjp'd Pallas
# kernel pair lowers to custom calls (no convolution/dot opcode, no
# ``transpose(`` marker on the custom_vjp backward), so the ONLY thing
# keeping its bytes in the right bucket and its backward in the bwd
# phase is the ``tpunet_<kernel>_{fwd,bwd}`` named scope around the
# kernel body. tpucheck rule R2 (tpunet/analysis/rules/scopes.py)
# imports this table and fails the tree when a kernel in tpunet/ops/
# is missing its scope or uses one this table doesn't know — so the
# attribution can't silently rot (the PR-6 failure class).
# ---------------------------------------------------------------------------

# Kernel scope prefix -> (forward category, backward category). The
# scope in the code must be exactly ``<prefix>_fwd`` / ``<prefix>_bwd``.
KERNEL_SCOPES: Dict[str, Tuple[str, str]] = {
    # Flash attention is MXU matmul work; without the marker its
    # custom calls land in ``elementwise`` and its custom_vjp backward
    # (no ``transpose(`` scope) would misattribute to the fwd phase.
    "tpunet_flash": ("matmul", "matmul"),
    # The serve engine's width-1 decode attention over the paged KV
    # pool (tpunet/ops/paged_decode.py): inference only, so there is a
    # _fwd scope and no backward.
    "tpunet_paged_decode": ("matmul", "matmul"),
}

# Scopes that mark a training phase directly (train/steps.py et al.).
PHASE_MARKERS: Tuple[Tuple[str, str], ...] = (
    ("tpunet_optimizer", "optimizer"),
    ("tpunet_ema", "ema"),
    ("tpunet_eval_forward", "eval"),
    ("tpunet_augment", "augment"),
)

_BWD_MARKERS = tuple(f"{p}_bwd" for p in KERNEL_SCOPES)


def _shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string (tuples sum their elements)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        nbytes = _DTYPE_BYTES.get(dtype)
        if nbytes is None:
            continue  # token[] / opaque[] / unknown
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * nbytes
    return total


def is_backward(op_name: str) -> bool:
    """True when the framework op name sits under an autodiff
    transpose scope (cotangent computation, remat replays included) or
    an explicit backward marker (the fused-IR / depthwise custom_vjp
    backwards, whose ops a custom-vjp rule does not nest under
    ``transpose(``)."""
    name = op_name or ""
    return ("transpose(" in name
            or any(m in name for m in _BWD_MARKERS))


def phase_of(op_name: str) -> str:
    """Training phase of a framework op name: fwd / bwd / optimizer /
    ema / eval / other — the split scripts/obs_report.py reports
    device time under."""
    name = op_name or ""
    for marker, phase in PHASE_MARKERS:
        if marker in name:
            return phase
    if "tpunet_fwd_bwd" in name or "jvp(" in name:
        return "bwd" if is_backward(name) else "fwd"
    return "other"


def _leaf_primitive(op_name: str) -> str:
    """Last path element of a framework op name ('.../bn/reduce_sum'
    -> 'reduce_sum')."""
    return (op_name or "").rsplit("/", 1)[-1]


def categorize(opcode: str, op_name: str) -> str:
    name = op_name or ""
    phase = phase_of(name)
    if phase in ("optimizer", "ema"):
        return "optimizer"
    if "tpunet_augment" in name:
        # Before the conv/dot checks: the rotation's shear matmul
        # banks are dots, but they are input-pipeline work.
        return "augment"
    for prefix, (fwd_cat, bwd_cat) in KERNEL_SCOPES.items():
        # The custom_vjp'd Pallas kernels lower to custom calls, not
        # convolution/dot opcodes; their explicit fwd/bwd scopes keep
        # them in the buckets the budget gates. (The tpunet_ prefix
        # keeps the match off the model's '/depthwise/' module path,
        # whose XLA convs the opcode branch below already handles.)
        if prefix in name:
            return bwd_cat if is_backward(name) else fwd_cat
    leaf = _leaf_primitive(name)
    if opcode == "convolution" or "conv_general_dilated" in leaf:
        return "conv_bwd" if is_backward(name) else "conv_fwd"
    if opcode == "dot" or leaf.startswith("dot_general"):
        return "matmul"
    # Multi-chip TPU modules print collectives as async pairs
    # (all-reduce-start / all-reduce-done); the -start carries the
    # traffic (the -done is skipped in the walk as a completion
    # marker).
    base_op = opcode[:-6] if opcode.endswith("-start") else opcode
    if base_op in _COLLECTIVES or leaf in _COLLECTIVES:
        return "collective"
    if "/bn/" in name:
        return "bn"
    if opcode in _COPY_ROOTS:
        return "copy_pad"
    if opcode in ("reduce", "reduce-window") or leaf.startswith("reduce"):
        return "reduce"
    return "elementwise"


def _computations(hlo_text: str) -> Tuple[Optional[str], Dict[str, List[str]]]:
    """Split module text into {computation name: [instruction lines]};
    returns (entry_name, comps)."""
    comps: Dict[str, List[str]] = {}
    entry = None
    current: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            name = m.group(2)
            if m.group(1):
                entry = name
            current = comps.setdefault(name, [])
            continue
        if line.startswith("}"):
            current = None
            continue
        if current is not None and _INSTR_RE.match(line):
            current.append(line)
    return entry, comps


def _instr_parts(line: str) -> Optional[Tuple[str, str, str, str, str]]:
    """-> (name, output type, opcode, operand segment, what follows
    the operands: attributes and metadata) or None."""
    m = _INSTR_RE.match(line)
    if not m:
        return None
    name, rest = m.group(1), m.group(2)
    # Output type: either a tuple "(...)" or a single token.
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        type_str, rest = rest[:i + 1], rest[i + 1:]
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        type_str, rest = rest[:sp], rest[sp:]
    rest = rest.lstrip()
    om = re.match(r"([\w\-]+)\(", rest)
    if not om:
        return None
    # Operand segment: the matching paren after the opcode. metadata/
    # attrs follow it, so quoted strings never reach the shape regex.
    depth, start = 0, om.end() - 1
    end = len(rest)
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    return name, type_str, om.group(1), rest[start + 1:end], rest[end:]


def _parse_instr(line: str, sizes: Dict[str, int]
                 ) -> Optional[Tuple[str, int, int, str]]:
    """-> (opcode, out_bytes, operand_bytes, op_name) or None.

    ``sizes`` maps the instruction names seen so far in this
    computation to their output bytes; this instruction is added. The
    HLO text jax 0.9.0 prints names operands without their types
    (``dot(%x.1, %w.1)``), so operand bytes are looked up by name;
    typed operands (``dot(f32[256,128]{1,0} %x.1, ...)``) are summed
    as printed."""
    parts = _instr_parts(line)
    if parts is None:
        return None
    name, type_str, opcode, args, tail = parts
    op_name = ""
    nm = _OPNAME_RE.search(tail)
    if nm:
        op_name = nm.group(1)
    out_bytes = sizes[name] = _shape_bytes(type_str)
    in_bytes = 0
    if opcode != "constant":
        in_bytes = _shape_bytes(args) or sum(
            sizes.get(tok, 0) for tok in _OPERAND_RE.findall(args))
    return opcode, out_bytes, in_bytes, op_name


def instruction_bytes(hlo_text: str) -> Iterator[Tuple[str, str, int, str]]:
    """Yield (opcode, category, bytes, op_name) per counted top-level
    instruction, walking ENTRY and any called (non-fused) bodies."""
    entry, comps = _computations(hlo_text)
    if entry is None:
        return
    seen = set()

    def walk(name: str) -> Iterator[Tuple[str, str, int, str]]:
        if name in seen or name not in comps:
            return
        seen.add(name)
        sizes: Dict[str, int] = {}
        for line in comps[name]:
            parsed = _parse_instr(line, sizes)
            if parsed is None:
                continue
            opcode, out_b, in_b, op_name = parsed
            if opcode in _SKIP_OPS:
                continue
            if opcode.endswith("-done"):
                # Async completion markers (all-reduce-done,
                # copy-done, async-done): the traffic was counted at
                # the matching -start; counting both halves would
                # double-charge every collective/async copy.
                continue
            if opcode in _CALL_OPS:
                for target in _called_comps(line):
                    yield from walk(target)
                continue
            yield opcode, categorize(opcode, op_name), out_b + in_b, op_name

    yield from walk(entry)


def _called_comps(line: str) -> List[str]:
    out = []
    for single, many in _CALLED_RE.findall(line):
        if single:
            out.append(single)
        if many:
            out.extend(t.strip().lstrip("%") for t in many.split(","))
    return out


_FUSED_RE = re.compile(r"\bcalls=%?([\w.\-]+)")


def _common_scope(paths: List[str]) -> str:
    """Longest common prefix of op_name paths, cut at a ``/``."""
    split = [p.split("/") for p in paths if p]
    if not split:
        return ""
    common = split[0]
    for parts in split[1:]:
        n = 0
        while n < min(len(common), len(parts)) and common[n] == parts[n]:
            n += 1
        common = common[:n]
    return "/".join(common)


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: op_name path} for the instructions a device
    trace shows (``fusion.59``, ``tpunet_flash_bwd.38``,
    ``copy.467``): ENTRY and the bodies it calls (while / conditional
    / call), never the inside of a fusion. A fusion takes its own
    ``metadata={op_name=...}``; where that is empty, the longest common
    scope prefix of the instructions in its fused computation. Any
    other instruction the compiler left without a name (a layout copy,
    the halves of an async copy) takes its first named operand's: it
    moves that operation's data. What still has no scope maps to
    ``""``."""
    entry, comps = _computations(hlo_text)
    out: Dict[str, str] = {}
    todo, seen = [entry] if entry else [], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            parts = _instr_parts(line)
            if parts is None:
                continue
            name, _type, _opcode, args, tail = parts
            om = _OPNAME_RE.search(tail)
            path = om.group(1) if om else ""
            fused = _FUSED_RE.search(tail)
            if not path and fused:
                path = _common_scope([
                    m.group(1) for m in map(_OPNAME_RE.search,
                                            comps.get(fused.group(1), []))
                    if m])
            if not path:
                path = next((out[tok] for tok in _OPERAND_RE.findall(args)
                             if out.get(tok)), "")
            out[name] = path
            todo.extend(_called_comps(line))
    return out


def breakdown(hlo_text: str) -> Dict[str, float]:
    """{category: total bytes} over the module, plus 'total'."""
    by_cat = {c: 0.0 for c in CATEGORIES}
    total = 0.0
    for _opcode, cat, nbytes, _name in instruction_bytes(hlo_text):
        by_cat[cat] = by_cat.get(cat, 0.0) + nbytes
        total += nbytes
    out = {k: v for k, v in by_cat.items() if v}
    out["total"] = total
    return out


def per_image_breakdown(hlo_text: str, images: int) -> Dict[str, int]:
    """Bytes per image by category ('total' included), from the
    per-device module text and the PER-DEVICE image count of one
    execution."""
    return {k: int(round(v / max(1, images)))
            for k, v in breakdown(hlo_text).items()}


def emit_gauges(registry, per_image: Dict[str, int]) -> None:
    """Mirror a per-image breakdown into the ``hbm_bytes_per_image_*``
    gauge family (snapshot keys usable in --obs-rule predicates)."""
    for cat, val in per_image.items():
        registry.gauge(f"hbm_bytes_per_image_{cat}").set(float(val))
