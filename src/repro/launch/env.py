"""Launch-environment tuning: the process-level knobs that must be set
BEFORE the interpreter (or at least before JAX initializes) to take effect.

The PR-1 ``host_parallel_efficiency`` probe showed host threads bottleneck
the pipelined sampler, and the usual large-model launch hygiene (tcmalloc
preloaded, XLA step markers, pinned math dtypes) is all pre-main
environment state — so it lives here as a *launcher*, not a library call:

    python -m repro.launch.env [--host-devices 8] -- python -m repro.launch.train ...

builds the tuned environment and ``exec``s the command under it. ``run.sh``
at the repo root is the shell-native equivalent for the common case.

Knobs (each reported by ``--report`` / skipped gracefully when unavailable):

* **tcmalloc** — ``LD_PRELOAD`` of libtcmalloc: the glibc allocator's arena
  contention is measurable with the pipeline's sampler/scheduler/dispatch
  threads all allocating; also raises the large-alloc report threshold so
  multi-GB table mmaps don't spam stderr. LD_PRELOAD only applies at
  process start — hence the exec-style launcher.
* **XLA_FLAGS** — ``--xla_step_marker_location=1`` (step markers at the
  fused train-step boundary, where the profiler and the §Observability
  span bridge expect them) and optionally
  ``--xla_force_host_platform_device_count=N`` for emulated-mesh runs
  (DESIGN.md §Sharding). Merged into any caller-set XLA_FLAGS without
  duplicating flags the caller already pinned.
* **thread pins** — OMP/MKL/OPENBLAS thread caps so host BLAS doesn't
  oversubscribe the cores the pipeline's own thread lanes need.
* **dtype pins** — ``JAX_ENABLE_X64=0`` + 32-bit default dtype bits: the
  engine's bit-identity contracts are all stated in fp32; a stray x64
  default would silently double every buffer.

Everything is additive to the caller's environment: a variable the caller
already set is NEVER overwritten (report says "kept").
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import shlex
import sys
from typing import Dict, List, Optional, Tuple

#: Common install locations for tcmalloc (gperftools / libtcmalloc-minimal).
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc_minimal.so.4",
    "/usr/lib64/libtcmalloc.so.4",
)

#: Sentinel guarding against the launcher re-exec'ing under itself.
_SENTINEL = "REPRO_ENV_LAUNCHED"


def find_tcmalloc() -> Optional[str]:
    for p in TCMALLOC_CANDIDATES:
        if os.path.exists(p):
            return p
    return None


def tcmalloc_active() -> bool:
    """Whether tcmalloc is actually mapped into THIS process (LD_PRELOAD
    must have been set before exec — setting it now does nothing)."""
    try:
        with open("/proc/self/maps") as f:
            return "tcmalloc" in f.read()
    except OSError:
        return False


def _merge_xla_flags(existing: str, wanted: List[str]) -> str:
    """Append wanted flags to an XLA_FLAGS string, skipping any flag (by
    ``--name=`` prefix) the existing string already pins."""
    have = {tok.split("=", 1)[0] for tok in existing.split() if tok}
    out = existing.split()
    for flag in wanted:
        if flag.split("=", 1)[0] not in have:
            out.append(flag)
    return " ".join(out)


@dataclasses.dataclass
class EnvPlan:
    """The computed environment delta + human-readable notes per knob."""

    env: Dict[str, str]
    notes: List[Tuple[str, str]]  # (knob, what happened)

    def apply(self, base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
        merged = dict(os.environ if base is None else base)
        merged.update(self.env)
        return merged

    def report(self) -> str:
        lines = ["launch-env plan:"]
        for knob, what in self.notes:
            lines.append(f"  {knob:<18} {what}")
        return "\n".join(lines)


def build_plan(host_devices: int = 0, threads: Optional[int] = None,
               tcmalloc: bool = True, step_marker: bool = True,
               pin_dtypes: bool = True,
               base: Optional[Dict[str, str]] = None) -> EnvPlan:
    """Compute the environment delta for a tuned launch. Never overwrites a
    variable the caller already set (the note records it as kept)."""
    cur = dict(os.environ if base is None else base)
    env: Dict[str, str] = {}
    notes: List[Tuple[str, str]] = []

    def want(key: str, val: str, why: str) -> None:
        if key in cur:
            notes.append((key, f"kept caller value {cur[key]!r}"))
        else:
            env[key] = val
            notes.append((key, f"{val!r}  ({why})"))

    if tcmalloc:
        lib = find_tcmalloc()
        if lib:
            want("LD_PRELOAD", lib, "arena-contention-free allocator for "
                 "the pipeline's host threads")
            want("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000",
                 "silence large-mmap reports for multi-GB tables")
        else:
            notes.append(("LD_PRELOAD", "skipped — no libtcmalloc found"))
    want("TF_CPP_MIN_LOG_LEVEL", "4", "quiet TF/XLA C++ banner noise")

    xla_wanted: List[str] = []
    if step_marker:
        xla_wanted.append("--xla_step_marker_location=1")
    if host_devices > 0:
        xla_wanted.append(
            f"--xla_force_host_platform_device_count={host_devices}")
    if xla_wanted:
        merged = _merge_xla_flags(cur.get("XLA_FLAGS", ""), xla_wanted)
        if merged != cur.get("XLA_FLAGS", ""):
            env["XLA_FLAGS"] = merged
            notes.append(("XLA_FLAGS", repr(merged)))
        else:
            notes.append(("XLA_FLAGS", "kept — caller already pins these"))

    if threads is not None and threads > 0:
        for var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS",
                    "MKL_NUM_THREADS"):
            want(var, str(threads),
                 "cap host BLAS so pipeline lanes keep their cores")

    if pin_dtypes:
        want("JAX_ENABLE_X64", "0", "fp32 bit-identity contracts")
        want("JAX_DEFAULT_DTYPE_BITS", "32", "no silent x64 buffers")

    return EnvPlan(env=env, notes=notes)


def current_report() -> Dict[str, object]:
    """What the CURRENT process actually launched with — printed by
    ``--report`` beside the plan, so it says which knobs are live."""
    return {
        "tcmalloc_active": tcmalloc_active(),
        "tcmalloc_found": find_tcmalloc(),
        "ld_preload": os.environ.get("LD_PRELOAD", ""),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "jax_enable_x64": os.environ.get("JAX_ENABLE_X64", ""),
        "omp_num_threads": os.environ.get("OMP_NUM_THREADS", ""),
        "autotune_cache": os.environ.get("REPRO_AUTOTUNE_CACHE", ""),
        "launched_via_env": _SENTINEL in os.environ,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.env",
        description="Build a tuned launch environment and exec a command "
                    "under it: python -m repro.launch.env [flags] -- cmd ...")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="emulate N host devices (XLA_FLAGS; 0 = off)")
    ap.add_argument("--threads", type=int, default=None,
                    help="cap OMP/MKL/OpenBLAS threads")
    ap.add_argument("--no-tcmalloc", action="store_true")
    ap.add_argument("--no-step-marker", action="store_true")
    ap.add_argument("--no-dtype-pins", action="store_true")
    ap.add_argument("--autotune-cache", default=None,
                    help=f"set {os.environ.get('REPRO_AUTOTUNE_CACHE', 'REPRO_AUTOTUNE_CACHE')!s} "
                         "for the child (persisted kernel-tile cache)")
    ap.add_argument("--report", action="store_true",
                    help="print the plan (and current-process state) and exit")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the plan + command without exec'ing")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="command to exec (prefix with --)")
    args = ap.parse_args(argv)

    plan = build_plan(host_devices=args.host_devices, threads=args.threads,
                      tcmalloc=not args.no_tcmalloc,
                      step_marker=not args.no_step_marker,
                      pin_dtypes=not args.no_dtype_pins)
    if args.autotune_cache:
        plan.env["REPRO_AUTOTUNE_CACHE"] = args.autotune_cache
        plan.notes.append(("REPRO_AUTOTUNE_CACHE", repr(args.autotune_cache)))

    if args.report:
        print(plan.report())
        for k, v in sorted(current_report().items()):
            print(f"  current: {k} = {v!r}")
        return 0

    cmd = list(args.cmd)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        print(plan.report())
        print("no command given — pass one after `--` (or use --report)",
              file=sys.stderr)
        return 2

    print(plan.report(), file=sys.stderr)
    if args.dry_run:
        print(f"would exec: {shlex.join(cmd)}", file=sys.stderr)
        return 0
    child_env = plan.apply()
    child_env[_SENTINEL] = "1"
    os.execvpe(cmd[0], cmd, child_env)
    return 0  # unreachable


if __name__ == "__main__":
    raise SystemExit(main())
