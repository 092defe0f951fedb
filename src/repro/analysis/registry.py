"""Hot-path program registry: every headline performance invariant of this
repo, declared as a machine-checkable contract next to the code it audits.

Each hot-path subsystem (``train.trainer``, ``core.wasap``, ``xl.stream``,
``serve.engine``, ``launch.steps``, ``kernels.sorted_segment_sum``) exposes an ``analysis_programs()`` hook
returning :class:`ProgramSpec` entries. A spec names a jitted program, knows
how to build it at a representative-but-CI-sized scale, and declares a
:class:`Contract` — what the jaxpr may contain, what the compiled HLO must
show (aliasing, temp bytes), and how many executables it may ever own.

``python -m repro.analysis`` audits every registered program
(``jaxpr_audit`` + ``hlo_audit``), and ``analysis.compilecheck`` lets tests
assert against the registry's expected-compile-count contracts instead of
hand-rolled ``_cache_size()`` arithmetic (DESIGN.md §10).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Callable, Dict, List, Optional, Tuple

# primitives that force a host round-trip (or arbitrary host code) inside a
# traced program — never acceptable in a registered hot path
HOST_CALLBACK_PRIMITIVES: Tuple[str, ...] = (
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
)

# modules whose ``analysis_programs()`` hook feeds the registry; order is
# the report order
HOOK_MODULES: Tuple[str, ...] = (
    "repro.train.trainer",
    "repro.core.wasap",
    "repro.xl.stream",
    "repro.serve.engine",
    "repro.launch.steps",
    "repro.kernels.sorted_segment_sum",
)


@dataclasses.dataclass(frozen=True)
class Contract:
    """The declared invariants of one hot-path program.

    jaxpr-level (checked by ``jaxpr_audit``):

    * ``forbidden_primitives`` — primitives that must not appear anywhere
      (host callbacks by default; add e.g. ``"sort"`` where a program
      guarantees sort-free dispatch).
    * ``max_unsorted_scatter`` / ``max_unsorted_scatter_elems`` — scatters
      with ``indices_are_sorted=False`` are the dense-scatter hazard the
      truly-sparse backward exists to avoid. Sorted segment-sum scatter-adds
      are the *designed* formulation and stay legal. The few allowed
      unsorted ones (e.g. the CE-loss label scatter) are bounded in count
      AND in per-op result size, so an nnz-sized scatter can never hide
      behind the allowance.
    * ``max_intermediate_elems`` — peak element count of any intermediate
      value; set from the chunk budget so a dense (batch, nnz)
      materialization beyond the budget fails the audit.
    * ``allow_f64`` — f64/c128 avals are dtype drift unless declared.

    compiled-HLO-level (checked by ``hlo_audit``):

    * ``donate_argnums`` / ``min_aliased_buffers`` — the audit force-builds
      the program with these argnums donated and requires at least this many
      input/output alias pairs in the compiled module header (donation that
      silently fails to alias is a dropped contract, not a warning).
      ``min_aliased_buffers=None`` derives the floor from the number of
      array leaves in the donated arguments.
    * ``max_temp_bytes`` — ceiling on ``memory_analysis().temp_size_in_bytes``.
    * ``max_hlo_scatter`` — backstop census of scatter opcodes in the
      compiled module (``None`` skips it: CPU's scatter expander rewrites
      scatters into loops, so the count is backend-dependent; the jaxpr
      check above is the authoritative one).

    lifecycle:

    * ``expected_compiles`` — executables this program may own after a
      double-call warmup (the zero-recompile contract; consumed by
      ``compilecheck`` in tests as well).
    """

    forbidden_primitives: Tuple[str, ...] = HOST_CALLBACK_PRIMITIVES
    max_unsorted_scatter: int = 0
    max_unsorted_scatter_elems: int = 0
    max_intermediate_elems: Optional[int] = None
    allow_f64: bool = False
    donate_argnums: Tuple[int, ...] = ()
    min_aliased_buffers: Optional[int] = None
    max_temp_bytes: Optional[int] = None
    max_hlo_scatter: Optional[int] = None
    expected_compiles: int = 1
    notes: str = ""


@dataclasses.dataclass
class AuditProgram:
    """A concrete, buildable instance of a registered program.

    ``make(donate)`` returns a FRESH jitted callable: ``donate=()`` for
    tracing / compile-count probes (safe to call twice on the same buffers),
    ``donate=contract.donate_argnums`` for the aliasing audit (lowered and
    compiled, never executed). ``args`` are example inputs at the spec's
    audit scale; ``kwargs`` carries static keyword args (``static_argnames``
    programs); ``meta`` carries the shape facts (batch, nnz, chunk, ...)
    the report prints next to the contract bounds.
    """

    make: Callable[[Tuple[int, ...]], Callable]
    args: Tuple
    kwargs: Dict[str, object] = dataclasses.field(default_factory=dict)
    meta: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ProgramSpec:
    name: str           # e.g. "train.segment" — stable waiver/report id
    subsystem: str      # registering module (dotted)
    contract: Contract
    build: Callable[[], AuditProgram]  # lazy: may construct models
    notes: str = ""


@functools.lru_cache(maxsize=1)
def collect() -> Tuple[ProgramSpec, ...]:
    """Import every hook module and gather its registered programs. Hooks
    must be cheap: model construction belongs in ``ProgramSpec.build``, not
    in the hook."""
    specs: List[ProgramSpec] = []
    seen: Dict[str, str] = {}
    for mod_name in HOOK_MODULES:
        mod = importlib.import_module(mod_name)
        hook = getattr(mod, "analysis_programs", None)
        if hook is None:
            raise RuntimeError(
                f"hot-path module {mod_name} lost its analysis_programs() "
                "registration hook"
            )
        for spec in hook():
            if spec.name in seen:
                raise RuntimeError(
                    f"duplicate program name {spec.name!r} "
                    f"({seen[spec.name]} and {mod_name})"
                )
            seen[spec.name] = mod_name
            specs.append(spec)
    return tuple(specs)


def get(name: str) -> ProgramSpec:
    for spec in collect():
        if spec.name == name:
            return spec
    raise KeyError(
        f"no registered hot-path program {name!r}; known: "
        f"{[s.name for s in collect()]}"
    )


def expected_compiles(name: str) -> int:
    """The registry's compile-count contract for ``name`` — the one source
    of truth the shared test helper (``compilecheck``) asserts against."""
    return get(name).contract.expected_compiles
