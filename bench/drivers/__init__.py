"""One driver per kind of traffic; a traffic file names its ``kind``.

A driver builds the program's objects for a cell from the seed (``setup``),
drives the timed path for a window (``window``), frees the program's state
(``release``) and compares what the window produced with the reference
(``check``).  Everything it sizes comes from the configuration and traffic
files, so a new cell of an existing kind is data alone; the model's
seeded weights come from the reference module the configuration file
names (``reference``), so a new architecture is that file and a module.
"""
from __future__ import annotations

import dataclasses
import importlib
import random
from typing import Any, Dict, List, Mapping, NamedTuple, Optional


class Check(NamedTuple):
    """A number compared with its limit; the run is correct only if every
    number is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Window:
    """What a window did: ``attempted`` and ``failed`` units of work (rounds
    or steps), its end-to-end values, and counters for the per-layer
    readers."""
    attempted: int
    failed: int
    values: Dict[str, float]
    counters: Dict[str, Any]


def checks_from(limits: Mapping[str, float], readings: Mapping[str, float]
                ) -> List[Check]:
    """The readings that have a limit, each as a ``Check``.  A traffic file
    names every reading; one whose limit is null is not compared."""
    missing = set(readings) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)} in the traffic file")
    return [Check(k, float(v), float(limits[k])) for k, v in readings.items()
            if limits[k] is not None]


def _replaced(obj, values: Mapping, where: str):
    """``obj`` (a dataclass) with ``values`` replaced: a mapping given for a
    field that holds a dataclass replaces that dataclass field by field.
    Every value is checked after the replacement."""
    fields = {f.name for f in dataclasses.fields(obj)}
    unknown = sorted(set(values) - fields)
    if unknown:
        raise ValueError(f"{where}: no field {unknown} in "
                         f"{type(obj).__name__}")
    new = {}
    for k, v in values.items():
        cur = getattr(obj, k)
        if dataclasses.is_dataclass(cur) and isinstance(v, Mapping):
            v = _replaced(cur, v, f"{where}.{k}")
        new[k] = v
    out = dataclasses.replace(obj, **new)
    for k, v in new.items():
        if getattr(out, k) != v:
            raise ValueError(f"{where}: {k} is {getattr(out, k)!r}, "
                             f"the file says {values[k]!r}")
    return out


def program_config(conf: Mapping):
    """The program's configuration object for a configuration file: the
    object ``program`` names with the file's ``as_run`` values replaced,
    checked field by field (a nested group, such as ``moe``, field by
    field too)."""
    mod, attr = conf["program"].split(":")
    base = getattr(importlib.import_module(mod), attr)
    return _replaced(base, conf["as_run"], conf["name"])


def reference_module(conf: Mapping):
    """The plain reference a configuration file names (``reference``, a
    module under ``bench/reference/``): it gives the seeded body,
    ``init_body(key, as_run)``, and its parameter count,
    ``body_params(as_run)``."""
    path = conf["reference"]
    if not (path.startswith("bench/reference/") and path.endswith(".py")):
        raise ValueError(f"{conf['name']}: reference {path!r} is not a "
                         "module under bench/reference/")
    return importlib.import_module(path[:-3].replace("/", "."))


class Reservoir:
    """A seeded uniform sample of ``k`` items from a stream of unknown
    length (algorithm R), plus the last item seen."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = random.Random(seed)
        self.items: List[Any] = []
        self.seen = 0
        self.last: Optional[Any] = None

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = self.rng.randrange(self.seen)
            if j < self.k:
                self.items[j] = item
        self.last = item

    def sample(self) -> List[Any]:
        out = list(self.items)
        if self.last is not None and all(x is not self.last for x in out):
            out.append(self.last)
        return out


def driver_for(kind: str):
    from . import finetune, fuse

    drivers = {"finetune": finetune.Finetune, "inproc": fuse.InProcess,
               "queue": fuse.Queue}
    if kind not in drivers:
        raise ValueError(f"no driver for traffic kind {kind!r}")
    return drivers[kind]
