"""Plugin registries: the ONE extension mechanism for C-DFL variants.

The paper's recipe (eq. 5 consensus composed with interchangeable
topologies, mixing weights and exchange schemes) is extensible by
construction, so every user-selectable scheme family is a named plugin
in a :class:`Registry` rather than a string branched on in some caller:

* :data:`transports`      — how the flat ``(K, P)`` buffer moves
  (``repro.core.transport``; entries are ``fed -> Transport`` factories);
* :data:`wire_codecs`     — how the buffer is represented ON the wire
  (``WireCodec`` instances: f32, bf16 today; int8+scales drops in here
  without touching any transport);
* :data:`mixing_policies` — eq. 6 weight rules on one (weighted)
  adjacency (``repro.core.topology``);
* :data:`mobility_traces` — kinematic trace generators
  (``repro.mobility.traces``);
* :data:`algorithms`      — trainer-level schemes
  (:class:`AlgorithmSpec` entries registered by ``repro.core.baselines``);
* :data:`fault_models`    — fault injectors compiled into device-resident
  per-round schedules (``repro.faults.models``);
* :data:`robust_rules`    — Byzantine-robust aggregation rules replacing
  the eq. 5 weighted mix (``repro.faults.robust``);
* :data:`redundancy_scenarios` — data-redundancy generators compiled
  into per-node item streams on the ingest path
  (``repro.ingest.scenarios``).

Registering a plugin is one decorator at its definition site::

    from repro.registry import mobility_traces

    @mobility_traces.register("convoy")
    def convoy_trace(rounds, k, *, speed=20.0, seed=0, **kw):
        ...

and the name immediately works everywhere a registered name does:
``MobilityConfig(kind="convoy")`` validates at construction,
``launch/train.py --mobility convoy`` appears in the CLI (choices are
derived from the registries), and ``Experiment``/``build_trainer``
dispatch to it — no edits outside the plugin.

This module imports nothing from ``repro`` at module scope (configs
validate against it from ``__post_init__``); the built-in plugins are
pulled in lazily by :func:`ensure_plugins`.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from typing import Any, Callable, Iterator, Optional


class Registry:
    """Name -> plugin mapping with decorator registration.

    Lookup failures list the registered names — the error a user sees
    when a config/CLI string has a typo, at construction time rather
    than deep inside trainer assembly.
    """

    def __init__(self, kind: str):
        self.kind = kind
        self._entries: dict[str, Any] = {}

    # -- registration -------------------------------------------------------
    def register(self, name: str, obj: Any = None, *,
                 overwrite: bool = False):
        """``register("x", obj)`` or ``@register("x")`` decorator form."""
        if obj is None:
            def deco(fn):
                self._add(name, fn, overwrite)
                return fn
            return deco
        self._add(name, obj, overwrite)
        return obj

    def _add(self, name: str, obj: Any, overwrite: bool) -> None:
        if not isinstance(name, str) or not name:
            raise ValueError(f"{self.kind} plugin name must be a non-empty "
                             f"string, got {name!r}")
        if name in self._entries and not overwrite:
            raise ValueError(
                f"{self.kind} {name!r} already registered "
                f"(pass overwrite=True to replace it)")
        self._entries[name] = obj

    def unregister(self, name: str) -> None:
        self._entries.pop(name, None)

    # -- lookup -------------------------------------------------------------
    def get(self, name: str) -> Any:
        try:
            return self._entries[name]
        except KeyError:
            raise ValueError(
                f"unknown {self.kind} {name!r} "
                f"(registered: {', '.join(self.names()) or '<none>'})"
            ) from None

    def validate(self, name: str) -> str:
        """Raise the listing :class:`ValueError` unless registered."""
        self.get(name)
        return name

    def names(self) -> tuple[str, ...]:
        return tuple(sorted(self._entries))

    def __contains__(self, name: object) -> bool:
        return name in self._entries

    def __iter__(self) -> Iterator[str]:
        return iter(self.names())

    def __len__(self) -> int:
        return len(self._entries)

    def __repr__(self) -> str:
        return f"Registry({self.kind!r}: {list(self.names())})"

    def view(self, transform: Optional[Callable] = None) -> "RegistryView":
        """Live read-only Mapping over the registry (back-compat for the
        module-level dicts the pre-registry API exposed)."""
        return RegistryView(self, transform)


class RegistryView(Mapping):
    """Read-only live Mapping facade over a :class:`Registry` — keeps
    legacy module attributes (``TRACE_KINDS``, ``ALGORITHMS``, ...)
    working, including for plugins registered after import."""

    def __init__(self, registry: Registry,
                 transform: Optional[Callable] = None):
        self._registry = registry
        self._transform = transform

    def __getitem__(self, name: str) -> Any:
        obj = self._registry.get(name)
        return self._transform(obj) if self._transform else obj

    def __iter__(self) -> Iterator[str]:
        return iter(self._registry)

    def __len__(self) -> int:
        return len(self._registry)

    def __contains__(self, name: object) -> bool:
        return name in self._registry

    def __repr__(self) -> str:
        return f"RegistryView({self._registry!r})"


@dataclasses.dataclass(frozen=True)
class AlgorithmSpec:
    """One trainer-level scheme (paper Sec. 5.3 and beyond).

    ``mixing``: the :data:`mixing_policies` name its exchange weights
    use. ``uses_transport``: False for schemes with no once-per-round
    flat-buffer exchange to route (fedavg's server average, dpsgd's
    per-step gossip). ``make``: trainer constructor with the
    ``(loss_fn, fed, train, **kw) -> Trainer`` signature.
    """

    name: str
    mixing: str
    uses_transport: bool
    make: Callable


# -- the registry instances --------------------------------------------------

transports = Registry("transport")
wire_codecs = Registry("wire codec")
mixing_policies = Registry("mixing policy")
mobility_traces = Registry("mobility trace")
algorithms = Registry("algorithm")
fault_models = Registry("fault model")
robust_rules = Registry("robust aggregation rule")
redundancy_scenarios = Registry("redundancy scenario")
leader_policies = Registry("leader policy")

ALL_REGISTRIES = {
    "transports": transports,
    "wire_codecs": wire_codecs,
    "mixing_policies": mixing_policies,
    "mobility_traces": mobility_traces,
    "algorithms": algorithms,
    "fault_models": fault_models,
    "robust_rules": robust_rules,
    "redundancy_scenarios": redundancy_scenarios,
    "leader_policies": leader_policies,
}

_PLUGINS_LOADED = False
_PLUGINS_LOADING = False


def ensure_plugins() -> None:
    """Import the built-in plugin modules (idempotent). Called lazily by
    config validation and the Experiment façade so that merely importing
    ``repro.registry`` stays dependency-free. A failed import is NOT
    latched: the next call retries, so the caller sees the real import
    error rather than permanently empty registries."""
    global _PLUGINS_LOADED, _PLUGINS_LOADING
    if _PLUGINS_LOADED or _PLUGINS_LOADING:
        return
    _PLUGINS_LOADING = True
    try:
        # Registration happens at each plugin's definition site; the
        # order here only matters for import-cycle hygiene (topology/
        # transport first, trainer-level last).
        import repro.core.topology    # noqa: F401  (mixing policies)
        import repro.core.transport   # noqa: F401  (transports, codecs)
        import repro.mobility.traces  # noqa: F401  (mobility traces)
        import repro.faults.models    # noqa: F401  (fault models)
        import repro.faults.robust    # noqa: F401  (robust rules)
        import repro.ingest.scenarios  # noqa: F401  (redundancy scenarios)
        import repro.ingest.weighting  # noqa: F401  ("redundancy" policy)
        import repro.hierarchy.leaders  # noqa: F401  (leader policies)
        import repro.core.baselines   # noqa: F401  (algorithms)
        _PLUGINS_LOADED = True
    finally:
        _PLUGINS_LOADING = False


# -- config validation (called from dataclass __post_init__) -----------------

def validate_fed_config(fed) -> None:
    """Every plugin name on a ``FedConfig`` must be registered — the
    error (listing valid names) fires at construction, not deep inside
    trainer assembly."""
    ensure_plugins()
    transports.validate(fed.transport)
    wire_codecs.validate(fed.wire_dtype)
    mixing_policies.validate(fed.mixing)
    algorithms.validate(fed.algorithm)
    if getattr(fed, "robust", None) is not None:
        robust_rules.validate(fed.robust)
    fmt = getattr(fed, "mixing_format", "dense")
    if fmt not in ("dense", "sparse", "hierarchical"):
        raise ValueError(f"unknown mixing_format {fmt!r} "
                         f"(choose from dense | sparse | hierarchical)")
    if getattr(fed, "hierarchy", None) is not None and fmt != "hierarchical":
        raise ValueError(
            "FedConfig.hierarchy is set but mixing_format is "
            f"{fmt!r} — hierarchy knobs only apply to "
            "mixing_format='hierarchical'")
    if fmt == "hierarchical":
        if fed.transport != "dense":
            raise ValueError(
                "mixing_format='hierarchical' requires the dense "
                "transport: the two-tier mix gathers arbitrary "
                "co-cluster and leader rows from the resident buffer "
                f"(got transport={fed.transport!r})")
        if getattr(fed, "robust", None) is not None:
            raise ValueError(
                "mixing_format='hierarchical' cannot combine with "
                "robust aggregation: robust rules rank the FULL dense "
                "neighbor column per coordinate "
                "(use mixing_format='dense')")
        if fed.algorithm in ("fedavg", "cdfa_m"):
            raise ValueError(
                f"mixing_format='hierarchical' does not apply to "
                f"algorithm={fed.algorithm!r}: fedavg has no "
                f"consensus exchange and cdfa_m mixes a dense layer "
                f"prefix (use cdfl | cfa | metropolis | dpsgd)")
    if fmt == "sparse":
        # degree bounds mirror topology.validate_degree (1 <= D <= K-1)
        from repro.core.topology import validate_degree
        validate_degree(fed.degree, fed.num_nodes)
        if fed.transport == "ring":
            raise ValueError(
                "mixing_format='sparse' needs a gather-capable transport "
                "(dense | gossip); the ring transport is physically "
                "degree-2 — its shifts ARE its topology")
        if getattr(fed, "robust", None) is not None:
            raise ValueError(
                "mixing_format='sparse' cannot combine with robust "
                "aggregation: robust rules rank the FULL dense neighbor "
                "column per coordinate (use mixing_format='dense')")


def validate_hierarchy_config(hier) -> None:
    ensure_plugins()
    leader_policies.validate(hier.leader_policy)
    if hier.max_cluster_size < 2:
        raise ValueError(f"max_cluster_size must be >= 2, "
                         f"got {hier.max_cluster_size}")
    if hier.inter_degree < 1:
        raise ValueError(f"inter_degree must be >= 1, "
                         f"got {hier.inter_degree}")
    if hier.remerge_burst < 0:
        raise ValueError(f"remerge_burst must be >= 0, "
                         f"got {hier.remerge_burst}")
    if hier.intra_rule is not None:
        mixing_policies.validate(hier.intra_rule)


def validate_fault_config(faults) -> None:
    ensure_plugins()
    for kind in faults.kinds:
        fault_models.validate(kind)


def validate_ingest_config(ing) -> None:
    ensure_plugins()
    if ing.scenario != "none":
        redundancy_scenarios.validate(ing.scenario)


def validate_mobility_config(mob) -> None:
    ensure_plugins()
    if mob.kind != "static":
        mobility_traces.validate(mob.kind)
    from repro.mobility.links import LINK_QUALITIES
    if mob.link_quality not in LINK_QUALITIES:
        raise ValueError(f"unknown link_quality {mob.link_quality!r} "
                         f"(choose from {LINK_QUALITIES})")
