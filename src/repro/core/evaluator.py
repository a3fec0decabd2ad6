"""Backend-selectable SCHED candidate evaluation.

One entry point — ``eval_candidates`` — scores a ``BatchedModelCandidates``
batch on one of three backends:

* ``numpy``   — ``cost.eval_model_candidates``, float64.  The parity oracle;
  also the fastest choice for small batches (no device dispatch).
* ``jax_ref`` — the jitted boundary-gather form in
  ``kernels.scar_eval.ops.evaluate``, float32.  The production path on
  hosts without an accelerator.
* ``pallas``  — the ``kernels.scar_eval`` Pallas kernel, float32 (TPU;
  ``interpret=True`` runs it anywhere for tests).

Both jax backends run ``cost.comm_from_parts`` on device — the literal
function the numpy oracle evaluates on host — so the comm geometry is
shared with the oracle by construction; shape-bucketed padding (S shrunk to
the per-batch max, B rounded up to ``EVAL_BLOCK_B``) keeps the jit cache to
a few shapes per (model, window).

Selection precedence: explicit ``backend=`` argument (``SearchConfig
.eval_backend`` everywhere in the pipeline) > ``SCAR_EVAL_BACKEND`` env var
> ``"auto"``.  ``auto`` dispatches on batch workload: below
``SCAR_EVAL_AUTO_THRESHOLD`` (B*Lw elements, default 32768 — the
measured numpy/jax crossover on CPU: 3x3 batches sit at <=9k, 16x16
path_cap=1024 batches at 50k-260k) the numpy oracle wins on dispatch
overhead; above it the jax path wins (Pallas when jax runs on a TPU,
``jax_ref`` otherwise) — this is what routes the 16x16 hot loop through
the kernel while 3x3 unit tests stay on numpy.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

from repro import obs

from .chiplet import MCM
from .cost import BatchedModelCandidates, eval_model_candidates
from .maestro import CostDB

BACKENDS = ("auto", "numpy", "jax_ref", "pallas")

# Shape-bucket compile accounting: the jax eval path recompiles once per
# distinct (backend, shapes, statics) signature; counting *new* signatures
# at the call site is deterministic and jax-version-independent, unlike
# polling jit cache internals.
_RECOMPILES = obs.counter("evaluator.jit_recompiles")
_SEEN_SIGNATURES: set[tuple] = set()

# Kernel batch block; pack_candidates pads B to a multiple of this.
EVAL_BLOCK_B = 128

# auto: batches below this many B*Lw elements stay on numpy.  Default for
# the SCAR_EVAL_AUTO_THRESHOLD env override, which (like SCAR_EVAL_BACKEND)
# is read per call so late setenv / monkeypatch takes effect.
AUTO_WORK_THRESHOLD = 32_768


def _auto_threshold() -> int:
    env = os.environ.get("SCAR_EVAL_AUTO_THRESHOLD", "").strip()
    return int(env) if env else AUTO_WORK_THRESHOLD

_JAX_PLATFORM: Optional[str] = None


def _jax_platform() -> str:
    """``jax.default_backend()``, resolved once per process.

    A JAX that cannot start raises here: the device path is never swapped
    for numpy behind the caller's back.
    """
    global _JAX_PLATFORM
    if _JAX_PLATFORM is None:
        import jax
        _JAX_PLATFORM = jax.default_backend()
    return _JAX_PLATFORM


def resolve_backend(backend: Optional[str] = None,
                    work: Optional[int] = None) -> str:
    """Concrete backend name for a request (see module docstring).

    ``work`` is the batch workload (B*Lw) the ``auto`` policy dispatches on;
    ``auto`` with no ``work`` resolves to the large-batch choice.
    """
    b = backend or "auto"
    if b == "auto":
        b = os.environ.get("SCAR_EVAL_BACKEND", "").strip() or "auto"
    if b not in BACKENDS:
        raise KeyError(f"unknown eval backend {b!r}; have {BACKENDS}")
    if b != "auto":
        return b
    if work is not None and work < _auto_threshold():
        return "numpy"
    return "pallas" if _jax_platform() == "tpu" else "jax_ref"


def eval_candidates(db: CostDB, mcm: MCM, cand: BatchedModelCandidates,
                    n_active: int, prev_end: Optional[int] = None,
                    pipelined: bool = True,
                    backend: Optional[str] = None,
                    interpret: bool = False,
                    comm_model: str = "analytic",
                    link_occ: Optional[np.ndarray] = None
                    ) -> tuple[np.ndarray, np.ndarray]:
    """``(lat[B], energy[B])`` float64 via the selected backend.

    Latencies are seconds, energies joules, for the ``B`` candidate plans
    in ``cand``.  The jax backends compute in float32 and are
    parity-tested against the numpy oracle within float32 tolerance (see
    ``tests/test_evaluator.py``); callers that need deterministic
    cross-backend ordering quantise scores before sorting
    (``sched.build_candidates``).

    ``comm_model="congestion"`` routes transfers over interposer links and
    prices contention with the background byte occupancy ``link_occ``
    (``[n_links]``, None = uncontended); every backend applies the same
    ``cost.congestion_correction`` terms.
    """
    B, Lw = cand.seg_id.shape
    resolved = resolve_backend(backend, work=B * Lw)
    if resolved == "numpy":
        with obs.span("eval_candidates", cat="evaluator", backend="numpy",
                      batch=B, layers=Lw):
            return eval_model_candidates(db, mcm, cand, n_active,
                                         prev_end=prev_end,
                                         pipelined=pipelined,
                                         comm_model=comm_model,
                                         link_occ=link_occ)
    if resolved == "pallas" and not interpret and _jax_platform() != "tpu":
        # fail fast with an actionable message instead of a lowering error
        # deep inside schedule(); tests run the kernel anywhere by passing
        # interpret=True
        raise RuntimeError(
            "eval backend 'pallas' needs a TPU (jax platform is "
            f"{_jax_platform()!r}); use 'jax_ref' here, or interpret=True "
            "for kernel tests")
    from repro.kernels.scar_eval import evaluate, pack_candidates
    from repro.launch import platform
    with obs.span("eval_candidates", cat="evaluator", backend=resolved,
                  batch=B, layers=Lw):
        args, statics, b_real = pack_candidates(db, mcm, cand, n_active,
                                                prev_end=prev_end,
                                                pad_b=EVAL_BLOCK_B,
                                                pipelined=pipelined,
                                                dense=(resolved == "pallas"),
                                                comm_model=comm_model,
                                                link_occ=link_occ)
        sig = (resolved, interpret,
               tuple((a.shape, str(a.dtype)) for a in args),
               tuple(sorted(statics.items())))
        if sig not in _SEEN_SIGNATURES:
            _SEEN_SIGNATURES.add(sig)
            _RECOMPILES.inc()
            obs.event("jit_compile", cat="evaluator", backend=resolved,
                      batch=int(args[5].shape[0]), layers=Lw)
        # the counted host-transfer point: one device->host sync per batch
        out = platform.device_fetch(
            evaluate(*args, **statics, block_b=EVAL_BLOCK_B,
                     interpret=interpret,
                     use_kernel=(resolved == "pallas")))
    return (out[:b_real, 0].astype(np.float64),
            out[:b_real, 1].astype(np.float64))


def traceable_scores(args, statics, *, use_kernel: bool = False,
                     interpret: bool = False):
    """In-jit (lat[B], energy[B]) for composition into a larger program.

    Takes the exact ``(args, statics)`` that ``pack_candidates`` produces
    and returns traced arrays instead of host numpy — the fused device
    search program (``engine.DeviceBeamEngine``) calls this under its own
    ``jax.jit`` so candidate scoring, beam combination and top-k selection
    compile into ONE device program with no intermediate host transfer.
    ``eval_candidates`` above is the standalone (host-returning) form of the
    same computation.
    """
    from repro.kernels.scar_eval import evaluate_traceable
    out = evaluate_traceable(*args, **statics, interpret=interpret,
                             use_kernel=use_kernel)
    return out[:, 0], out[:, 1]
