"""Compiled-program inspection: one home for every HLO-text property check.

The paper's portability argument is program-level: fusion, data movement,
and collective traffic decide whether a port is fast, and those properties
live in the *compiled* program, not the Python source. PR 9 asserted one of
them (collective counts) with an inline ``txt.count(...)`` inside a test.
This module is the shared API the tests and the contract auditor
(``repro.analysis.audit``) read compiled programs through:

  collective_counts    : instructions per collective kind (all-reduce,
                         reduce-scatter, all-to-all, all-gather,
                         collective-permute), ``-start`` forms merged and
                         ``-done`` forms skipped so async pairs count once.
  dtype_census         : instruction-output dtypes -> instruction count
                         (the f64-creep / bf16-accumulation detector).
  scatter_output_dtypes: output dtypes of scatter accumulations (the
                         "bf16 paths must accumulate in f32" check).
  host_call_count      : host round-trips compiled INTO the program —
                         python-callback custom-calls, infeed/outfeed,
                         host-transfer send/recv. Must be 0 on jitted paths.
  realized_alias_count : input->output aliases the executable actually
                         established (donation that *took*).
  donated_arg_count    : donation *requested* at the jit boundary (counted
                         from ``Lowered.args_info`` — a donated-but-
                         unaliasable buffer still counts, so disabling
                         ``donate_argnums`` is visible even when shapes
                         never alias).
  recompile_misses     : jit-cache misses beyond the first call across
                         repeated same-shape calls (the silent-recompile
                         detector).

Everything here is text/duck-typed on purpose: no jax import, so the module
loads anywhere (including the jax-free lint CI path) and works on HLO text
from any source — a live ``Compiled``, a golden file, a subprocess pipe.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Iterator, Set, Tuple

#: collective instruction kinds, the cross-device data-movement vocabulary
COLLECTIVE_KINDS = ("all-gather", "all-reduce", "all-to-all",
                    "collective-permute", "reduce-scatter")

#: dtype tokens that appear in HLO shapes
DTYPE_TOKENS = ("pred", "s4", "s8", "s16", "s32", "s64", "u4", "u8", "u16",
                "u32", "u64", "f8e4m3fn", "f8e5m2", "f16", "bf16", "f32",
                "f64", "c64", "c128")

# "  %name = f32[2,3]{1,0} opcode(...)" / "  ROOT %r = (f32[], pred[]) op(...)"
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?(?:%[\w.\-]+|[\w.\-]+)\s*=\s*(.+?)\s+"
                       r"([\w\-]+)\(")
_DTYPE_RE = re.compile(r"\b(%s)\[" % "|".join(DTYPE_TOKENS))
# one "{out_index}: (param, {param_index}, kind)" entry per realized alias
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9,\s]*\}:\s*\(\d+")
# the map nests one level of {output_index} braces: match them explicitly
# (a lazy .*? would stop at the FIRST nested '}' and undercount)
_ALIAS_MAP_RE = re.compile(
    r"input_output_alias=\{((?:[^{}]|\{[0-9,\s]*\})*)\}")
_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')

#: custom-call targets that round-trip through the host python runtime
_HOST_CALLBACK_MARKERS = ("callback", "py_func", "host_func")


def iter_instructions(hlo: str) -> Iterator[Tuple[str, str, str]]:
    """Yield ``(opcode, output_type_str, full_line)`` per HLO instruction."""
    for line in hlo.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            yield m.group(2), m.group(1), line


def collective_counts(hlo: str) -> Dict[str, int]:
    """Instructions per collective kind (every kind present, zeros kept).

    Async pairs count once: ``all-reduce-start`` folds into ``all-reduce``
    and the matching ``-done`` is skipped — so the count is the number of
    collective *operations* the program performs per execution, which is
    what the paper's data-movement budget (and PR 9's one-chain-per-step
    property) cares about.
    """
    counts = {k: 0 for k in COLLECTIVE_KINDS}
    for op, _, _ in iter_instructions(hlo):
        if op.endswith("-done"):
            continue
        base = op[:-len("-start")] if op.endswith("-start") else op
        if base in counts:
            counts[base] += 1
    return counts


def dtype_census(hlo: str) -> Dict[str, int]:
    """Instruction count per output dtype appearing anywhere in the program.

    Tuple-typed outputs contribute every element dtype. The census is the
    f64-creep detector: a single f64 instruction in a production program
    means a literal, an accidental numpy promotion, or an x64 leak doubled
    someone's memory traffic.
    """
    census: Dict[str, int] = {}
    for _, type_str, _ in iter_instructions(hlo):
        for dt in _DTYPE_RE.findall(type_str):
            census[dt] = census.get(dt, 0) + 1
    return census


def scatter_output_dtypes(hlo: str) -> Set[str]:
    """Output dtypes of ``scatter`` instructions (the accumulation ops).

    The repo's bf16 strategies keep *patches* in bf16 but must accumulate
    the charge grid in f32 (PR 3's memory-traffic contract); a bf16 scatter
    output means someone dropped the upcast.

    CPU caveat: XLA's scatter expander rewrites scatter into dynamic-
    update-slice loops on CPU, so the set is typically empty there — the
    check has teeth on the accelerator backends, where scatter survives
    (the dtype census still catches bf16 *presence* everywhere).
    """
    out: Set[str] = set()
    for op, type_str, _ in iter_instructions(hlo):
        if op == "scatter":
            out.update(_DTYPE_RE.findall(type_str))
    return out


def host_call_count(hlo: str) -> int:
    """Host round-trips compiled into the program (must be 0 in jitted
    production paths): python-callback custom-calls, infeed/outfeed, and
    host-transfer send/recv. Backend FFT/linalg custom-calls (ducc_fft,
    lapack, cublas, ...) are device-side and do NOT count."""
    n = 0
    for op, _, line in iter_instructions(hlo):
        if op in ("infeed", "outfeed"):
            n += 1
        elif op in ("send", "recv") and "is_host_transfer=true" in line:
            n += 1
        elif op == "custom-call":
            m = _TARGET_RE.search(line)
            target = (m.group(1) if m else "").lower()
            if any(s in target for s in _HOST_CALLBACK_MARKERS):
                n += 1
    return n


def realized_alias_count(hlo: str) -> int:
    """Input->output aliases the compiled executable established.

    Parsed from the module header's ``input_output_alias={ ... }`` map; a
    program whose donation never took (no shape/dtype-compatible output)
    has no header entry and counts 0 — pair with ``donated_arg_count`` to
    tell "donation disabled" apart from "donation unusable"."""
    m = _ALIAS_MAP_RE.search(hlo)
    if not m:
        return 0
    return len(_ALIAS_ENTRY_RE.findall(m.group(1)))


def donated_arg_count(lowered) -> int:
    """Number of donated argument buffers of a ``jax.stages.Lowered``.

    Counted from ``args_info`` (the jit-boundary donation *request*), so it
    is independent of whether XLA could alias anything — removing
    ``donate_argnums`` from an executor changes this count even when every
    realized alias count was already 0.
    """
    import jax  # local: keep this module importable without jax

    n = 0
    for info in jax.tree.leaves(lowered.args_info,
                                is_leaf=lambda x: hasattr(x, "donated")):
        n += bool(getattr(info, "donated", False))
    return n


def recompile_misses(jitfn, make_args: Callable[[int], tuple],
                     calls: int = 2) -> int:
    """Jit-cache misses beyond the first call across ``calls`` same-shape
    calls of ``jitfn`` (``make_args(i)`` builds FRESH operands per call, so
    donated buffers are never re-used). 0 means the program is trace-stable;
    anything else is a silent recompile — a weak-typed literal flipping per
    call, a python-hashed closure, a shape leak."""
    import jax

    before = jitfn._cache_size()
    for i in range(calls):
        out = jitfn(*make_args(i))
        jax.block_until_ready(out)
    return max(jitfn._cache_size() - before, 1) - 1
