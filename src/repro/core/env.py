"""Environment-variable parsing shared by every QUIP_* gate.

The serving and imputation layers are gated by boolean env vars
(``QUIP_SHARED_IMPUTE``, ``QUIP_IMPUTE_BATCH``).  Each used to parse the
raw string ad hoc — ``resolve_shared_impute`` accepted only the literal
``"1"``, so ``QUIP_SHARED_IMPUTE=true`` silently left sharing *off*.
:func:`env_flag` is the one shared parser: the usual truthy/falsy spellings
work, anything else fails loud instead of silently picking a default.

:func:`env_choice` is the enumerated-value twin for the implementation
dispatch vars (``QUIP_JOIN_IMPL``, ``QUIP_KNN_IMPL``, ``QUIP_EXEC_IMPL``,
``QUIP_SEGMENT_IMPL``): each call site used to hand-parse
``impl or os.environ.get(...) or default`` and a typo'd value raised only
*after* silently skipping the env var's precedence rules; now garbage
fails loud with the variable name and the accepted spellings, exactly
like ``env_flag``.

:func:`env_int` is the integer sibling (``QUIP_FUZZ_SEED``): unset means
the default, garbage raises instead of silently falling back.

:data:`ENV_REGISTRY` is the one catalog of every ``QUIP_*`` knob the tree
reads — name, kind, default, accepted values, owning module, one-line doc.
The quiplint env-discipline pass (``repro.analysis``) enforces that every
``QUIP_*`` read goes through the parsers above against a registered name,
and that the generated table in ``docs/analysis.md`` matches this registry
exactly; an unregistered knob (or a registered-but-undocumented one) fails
CI.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Sequence, Tuple

__all__ = ["ENV_REGISTRY", "EnvKnob", "env_flag", "env_choice", "env_int"]

_TRUE = frozenset({"1", "true", "yes", "on"})
_FALSE = frozenset({"0", "false", "no", "off"})


def env_flag(name: str, default: bool) -> bool:
    """Boolean env var ``name``: 1/true/yes/on ↔ 0/false/no/off (any case).

    Unset (or empty) returns ``default``; any other value raises
    ``ValueError`` — a typo'd gate must not silently mean "off".
    """
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return bool(default)
    value = raw.strip().lower()
    if value in _TRUE:
        return True
    if value in _FALSE:
        return False
    raise ValueError(
        f"{name}={raw!r} is not a boolean flag "
        f"(expected one of {sorted(_TRUE)} or {sorted(_FALSE)})"
    )


def env_choice(name: str, choices: Sequence[str], default: str) -> str:
    """Enumerated env var ``name``: one of ``choices`` (any case).

    Unset (or empty) returns ``default``; any other value raises
    ``ValueError`` — a typo'd impl name must not silently pick a default.
    """
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    value = raw.strip().lower()
    if value in choices:
        return value
    raise ValueError(
        f"{name}={raw!r} is not a valid choice (expected one of {sorted(choices)})"
    )


def env_int(name: str, default: Optional[int] = None) -> Optional[int]:
    """Integer env var ``name`` (e.g. ``QUIP_FUZZ_SEED``).

    Unset (or empty) returns ``default``; any non-integer value raises
    ``ValueError`` — a typo'd seed must not silently fall back to the
    default sweep.
    """
    raw = os.environ.get(name)
    if raw is None or raw.strip() == "":
        return default
    try:
        return int(raw.strip())
    except ValueError:
        raise ValueError(
            f"{name}={raw!r} is not an integer"
        ) from None


# --------------------------------------------------------------------------- #
# the QUIP_* knob registry
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class EnvKnob:
    """One registered ``QUIP_*`` environment knob.

    ``kind`` is the parser family (``flag`` | ``choice`` | ``int``);
    ``default`` is the human-readable unset behaviour; ``choices`` lists
    the accepted spellings for ``choice`` knobs; ``owner`` names the module
    whose resolver reads it."""

    name: str
    kind: str
    default: str
    doc: str
    choices: Tuple[str, ...] = ()
    owner: str = ""


def _registry(*knobs: EnvKnob) -> Dict[str, EnvKnob]:
    out: Dict[str, EnvKnob] = {}
    for knob in knobs:
        if knob.name in out:
            raise ValueError(f"duplicate ENV_REGISTRY knob {knob.name}")
        out[knob.name] = knob
    return out


#: Every QUIP_* knob the tree reads.  quiplint's env-discipline pass fails
#: on any env_flag/env_choice/env_int call naming a QUIP_* variable that is
#: not listed here, on any registered knob with no read site, and on any
#: drift between this registry and the table in docs/analysis.md.
ENV_REGISTRY: Dict[str, EnvKnob] = _registry(
    EnvKnob("QUIP_SHARED_IMPUTE", "flag", "off",
            "cross-query imputation sharing (one ImputeStore for all "
            "sessions)", owner="service/impute_store.py"),
    EnvKnob("QUIP_IMPUTE_BATCH", "flag", "on",
            "batched request-queue imputation (off = per-call flushes)",
            owner="imputers/base.py"),
    EnvKnob("QUIP_JOIN_IMPL", "choice", "numpy (engine) / auto (kernel)",
            "join-spine dispatch: numpy sort-join oracle, jnp ref, or the "
            "Pallas open-addressing kernels (PALLAS_RUNS runs ref for "
            "them on TPU); unset means numpy in the engine "
            "(core/triggers.py) and the backend default in the kernel "
            "wrapper (kernels/ops.py)",
            choices=("numpy", "ref", "pallas"),
            owner="core/triggers.py, kernels/ops.py"),
    EnvKnob("QUIP_KNN_IMPL", "choice", "numpy",
            "KNN neighbour-aggregation dispatch (mean/mode)",
            choices=("numpy", "ref", "pallas"), owner="kernels/ops.py"),
    EnvKnob("QUIP_SEGMENT_IMPL", "choice", "numpy",
            "grouped-aggregate segment-reduction dispatch",
            choices=("numpy", "ref", "pallas"), owner="kernels/ops.py"),
    EnvKnob("QUIP_BLOOM_IMPL", "choice",
            "auto (kernels/ops.py backend table: pallas on TPU, ref on CPU)",
            "bloom-probe dispatch for join pruning; pallas runs what "
            "kernels/ops.py PALLAS_RUNS names for the backend",
            choices=("numpy", "ref", "pallas"), owner="kernels/ops.py"),
    EnvKnob("QUIP_DIST_IMPL", "choice",
            "auto (kernels/ops.py backend table: pallas on TPU, ref on CPU)",
            "masked KNN partial-distance dispatch; pallas runs what "
            "kernels/ops.py PALLAS_RUNS names for the backend",
            choices=("numpy", "ref", "pallas"), owner="kernels/ops.py"),
    EnvKnob("QUIP_EXEC_IMPL", "choice", "interp",
            "executor dispatch: morsel interpreter or compiled tensor "
            "plans", choices=("interp", "compiled"),
            owner="core/compiled.py"),
    EnvKnob("QUIP_TRACE", "flag", "off",
            "span tracing (Chrome-trace/Perfetto export)",
            owner="obs/trace.py"),
    EnvKnob("QUIP_TRACE_CLOCK", "choice", "wall",
            "span-tracer clock: wall seconds or the deterministic unit "
            "tick", choices=("wall", "unit"), owner="obs/trace.py"),
    EnvKnob("QUIP_EXPLAIN", "flag", "off",
            "per-query impute-provenance recording (explain reports)",
            owner="obs/provenance.py"),
    EnvKnob("QUIP_IVM", "flag", "off",
            "delta-driven result-cache maintenance: patch cached answers "
            "under registry mutations instead of evicting them",
            owner="service/ivm.py"),
    EnvKnob("QUIP_FUZZ_SEED", "int", "unset",
            "extra seed injected into the serving-fuzzer sweeps (CI "
            "repro)", owner="tests/test_serving_fuzz.py"),
    EnvKnob("QUIP_SANITIZE", "choice", "off",
            "runtime sanitizers: 'locks' swaps every lock site for "
            "instrumented wrappers feeding the lock-order graph",
            choices=("off", "locks"), owner="analysis/lockcheck.py"),
)
