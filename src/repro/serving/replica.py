"""Mesh-slice placement for replica groups.

A deployment may run N engine replicas, each placed on a disjoint device
slice. The ``mesh_slice`` deploy knob — previously recorded as free text
and never read — is parsed here into a :class:`MeshPlacement`: one
:class:`ReplicaSlice` per replica, validated (well-formed, in range,
pairwise disjoint) before any deployment is torn down.

Grammar (comma-separated atoms)::

    mesh_slice := "auto" | atom ("," atom)*
    atom       := "devices:" N [ "-" M ]          # physical device indices
                | "pod" P "/rows" A [ "-" B ]     # topology rows (launch/mesh.py)

- ``auto`` (or omitting the knob): the live devices are partitioned
  evenly across replicas; with fewer devices than replicas the placement
  is *oversubscribed* (replicas share devices round-robin) — the CPU
  test platform has one device unless ``XLA_FLAGS`` forces more.
- one atom with N replicas: the deployment's overall slice, partitioned
  contiguously across the replicas.
- N atoms with N replicas: explicit per-replica slices.

Physical atoms are validated against the live device count; topology
atoms are validated against the production geometry (``launch/mesh.py``)
and *fold* onto the live devices modulo the device count at bind time,
so a "pod0/rows0-7" deployment exercises the same code path on 8 forced
host devices in CI as on 128 chips in production. Disjointness is
checked in the space the spec names — mixing physical and topology atoms
in one spec is rejected (their index spaces are not comparable).

This module's parsing is pure (no jax): device binding and the live
device count import lazily, so validation can run anywhere.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple


class MeshSliceError(ValueError):
    """Malformed, out-of-range, or overlapping ``mesh_slice`` spec —
    surfaced by the API layer as a structured 400 ``INVALID_MESH_SLICE``."""


_DEVICES_RE = re.compile(r"devices:(\d+)(?:-(\d+))?$")
_POD_ROWS_RE = re.compile(r"pod(\d+)/rows(\d+)(?:-(\d+))?$")


def live_device_count() -> int:
    """Number of addressable devices right now. A JAX that cannot start
    raises here: a placement is never validated against a guessed count."""
    import jax
    return jax.device_count()


@dataclass(frozen=True)
class ReplicaSlice:
    """One replica's device slice: flat indices in either physical
    (``jax.devices()`` order) or logical (topology chip) space."""

    label: str                  # canonical text, e.g. "devices:0-3"
    chips: Tuple[int, ...]      # flat indices, ascending
    logical: bool = False       # True: topology chip space (folds at bind)

    def bind(self, devices: Sequence[Any]) -> Tuple[Any, ...]:
        """Resolve to live device objects. Logical slices fold modulo the
        device count (production geometry on a small test platform);
        physical indices were range-checked at parse time."""
        if self.logical:
            return tuple(devices[i % len(devices)] for i in self.chips)
        return tuple(devices[i] for i in self.chips)

    def to_json(self) -> Dict[str, Any]:
        return {"slice": self.label, "chips": len(self.chips),
                "logical": self.logical}


@dataclass(frozen=True)
class MeshPlacement:
    """Validated per-replica placement for one deployment."""

    spec: Optional[str]                 # the spec text as given (None=auto)
    slices: Tuple[ReplicaSlice, ...]    # one per replica
    oversubscribed: bool = False        # replicas share devices (test CPU)

    @property
    def replicas(self) -> int:
        return len(self.slices)

    def describe(self) -> List[Dict[str, Any]]:
        out = []
        for i, s in enumerate(self.slices):
            d = s.to_json()
            d["replica"] = f"r{i}"
            out.append(d)
        return out


def _parse_atom(atom: str) -> ReplicaSlice:
    m = _DEVICES_RE.match(atom)
    if m:
        lo = int(m.group(1))
        hi = int(m.group(2)) if m.group(2) is not None else lo
        if hi < lo:
            raise MeshSliceError(
                f"bad device range {atom!r}: {hi} < {lo}")
        return ReplicaSlice(label=atom, chips=tuple(range(lo, hi + 1)))
    m = _POD_ROWS_RE.match(atom)
    if m:
        from repro.launch.mesh import pod_row_chips
        pod = int(m.group(1))
        lo = int(m.group(2))
        hi = int(m.group(3)) if m.group(3) is not None else lo
        try:
            chips = pod_row_chips(pod, lo, hi)
        except ValueError as e:
            raise MeshSliceError(f"bad topology slice {atom!r}: {e}") \
                from None
        return ReplicaSlice(label=atom, chips=chips, logical=True)
    raise MeshSliceError(
        f"unparseable mesh_slice atom {atom!r} (expected 'auto', "
        "'devices:A[-B]', or 'podP/rowsA[-B]')")


def _partition(chips: Tuple[int, ...], parts: int
               ) -> List[Tuple[int, ...]]:
    """Split ``chips`` into ``parts`` contiguous, near-even chunks; with
    fewer chips than parts the chips are reused round-robin."""
    n = len(chips)
    if n >= parts:
        out, start = [], 0
        for i in range(parts):
            size = n // parts + (1 if i < n % parts else 0)
            out.append(chips[start:start + size])
            start += size
        return out
    return [(chips[i % n],) for i in range(parts)]


def _auto_placement(replicas: int, device_count: int) -> MeshPlacement:
    chunks = _partition(tuple(range(device_count)), replicas)
    over = device_count < replicas
    slices = []
    for ch in chunks:
        label = (f"devices:{ch[0]}" if len(ch) == 1
                 else f"devices:{ch[0]}-{ch[-1]}")
        slices.append(ReplicaSlice(label=label, chips=ch))
    return MeshPlacement(spec=None, slices=tuple(slices),
                         oversubscribed=over)


def parse_mesh_slice(spec: Optional[str], *, replicas: int = 1,
                     device_count: Optional[int] = None) -> MeshPlacement:
    """Parse and validate a ``mesh_slice`` spec for ``replicas`` replicas.

    Raises :class:`MeshSliceError` on malformed atoms, out-of-range
    indices, overlapping slices, or a slice count that matches neither 1
    nor ``replicas``.
    """
    if not isinstance(replicas, int) or isinstance(replicas, bool) \
            or replicas < 1:
        raise MeshSliceError(f"replicas must be a positive integer, "
                             f"got {replicas!r}")
    if device_count is None:
        device_count = live_device_count()
    if spec is None or (isinstance(spec, str)
                        and spec.strip().lower() in ("", "auto")):
        return _auto_placement(replicas, device_count)
    if not isinstance(spec, str):
        raise MeshSliceError(
            f"mesh_slice must be a string, got {type(spec).__name__}")
    atoms = [a.strip() for a in spec.split(",")]
    if not all(atoms):
        raise MeshSliceError(f"empty atom in mesh_slice spec {spec!r}")
    slices = [_parse_atom(a) for a in atoms]
    if len({s.logical for s in slices}) > 1:
        raise MeshSliceError(
            f"mesh_slice {spec!r} mixes physical (devices:) and topology "
            "(pod/rows) atoms; their index spaces are not comparable")
    logical = slices[0].logical
    # physical indices must address live devices (the bugfix this parser
    # exists for: free text used to be recorded and never checked)
    if not logical:
        for s in slices:
            if s.chips[-1] >= device_count:
                raise MeshSliceError(
                    f"slice {s.label!r} addresses device {s.chips[-1]} "
                    f"but only {device_count} device(s) exist")
    # disjointness in the spec's own index space
    seen: Dict[int, str] = {}
    for s in slices:
        for c in s.chips:
            if c in seen:
                raise MeshSliceError(
                    f"overlapping slices: {seen[c]!r} and {s.label!r} "
                    f"both claim chip {c}")
            seen[c] = s.label
    if len(slices) == replicas:
        return MeshPlacement(spec=spec, slices=tuple(slices))
    if len(slices) == 1:
        # one deployment-wide slice, partitioned across the replicas
        chunks = _partition(slices[0].chips, replicas)
        over = len(slices[0].chips) < replicas
        subs = tuple(
            ReplicaSlice(label=f"{slices[0].label}[{i}/{replicas}]",
                         chips=ch, logical=logical)
            for i, ch in enumerate(chunks))
        return MeshPlacement(spec=spec, slices=subs, oversubscribed=over)
    raise MeshSliceError(
        f"mesh_slice {spec!r} has {len(slices)} slices for "
        f"{replicas} replica(s) — give one slice (partitioned evenly) "
        "or exactly one per replica")
