"""The one traffic generator: a mix is a data file ``bench/traffic/<mix>.json``
whose ``"kind"`` names the module ``bench/kinds/<kind>.py`` that drives it.

A kind module holds what one kind of traffic does, found by that name
alone, so a new kind is a new file and a new mix of a known kind is data:

``KEYS``         the mix keys it reads, each with its type or its choices;
``LIMITS``       the names under the mix's ``"limits"``;
``COUNTS``       what the window's ``completed`` counts (``"apply"``...);
``view``         the CSR arrays of the matrix the traffic applies;
``target``       what the window calls (the operator, or its transpose);
``make_inputs``, ``warm_up``, ``run_window``, ``compare``.

Every seed gives the same amount of work: the same matrix size, the same
number of operands and the same solve length; only values differ.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import os
from typing import Callable, List

KINDS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kinds")


@dataclasses.dataclass
class Window:
    """What one measured window did, on the host clock."""

    counts: str = ""              # the kind's COUNTS
    seconds: float = 0.0          # first start to last counted completion
    completed: int = 0            # applies, or CG iterations, counted
    attempted: int = 0            # applies, or CG sets, started
    failed: int = 0               # of those, how many raised
    latencies: List[float] = dataclasses.field(default_factory=list)  # walls
    spmv_s: float = 0.0           # CG: time inside the spmv callable
    answers: list = dataclasses.field(default_factory=list)
    errors: List[str] = dataclasses.field(default_factory=list)


def span_factory(
        tracing: bool) -> Callable[[str], contextlib.AbstractContextManager]:
    """``span(name)``: a profiler annotation while tracing, else nothing."""
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def load_kind(mix: dict, kinds: str = KINDS):
    """The module of the mix's kind, after the mix has been checked
    against it: every key it reads is there with a value it supports,
    and there is no key it would not read."""
    name = mix.get("kind", "")
    path = os.path.join(kinds, f"{name}.py")
    if not name or not os.path.isfile(path):
        raise ValueError(f"no traffic kind {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_kind_{name}", path)
    kind = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(kind)
    extra = set(mix) - set(kind.KEYS) - {"kind", "limits"}
    if extra:
        raise ValueError(f"mix keys {sorted(extra)} are not read by kind "
                         f"{name!r}, which reads {sorted(kind.KEYS)}")
    for key, want in kind.KEYS.items():
        value = mix.get(key)
        ok = (value in want if isinstance(want, tuple)
              else isinstance(value, want) and not isinstance(value, bool))
        if not ok:
            raise ValueError(f"mix key {key!r} = {value!r}: kind {name!r} "
                             f"wants {want}")
    if set(mix.get("limits", {})) != set(kind.LIMITS):
        raise ValueError(f"mix limits {sorted(mix.get('limits', {}))}: kind "
                         f"{name!r} compares {list(kind.LIMITS)}")
    return kind


def passed(check: dict) -> bool:
    v = check["value"]
    if check.get("at_least"):
        return v >= check["limit"]
    return v <= check["limit"]
