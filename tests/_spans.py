"""Program spans read back from a ``jax.profiler`` session on the CPU.

``with record(dir) as rec:`` runs a session (``repro.obs.trace.session``);
after the block ``rec.spans`` holds every span the program and the
runtime recorded on the host, each with the thread line it ran on and its
metadata, as the benchmark's trace reduction sees them.
"""
from __future__ import annotations

import glob
from contextlib import contextmanager
from dataclasses import dataclass, field

from repro.obs.trace import session


@dataclass
class Span:
    name: str
    start: int            # ns on the profiler's clock
    end: int
    line: tuple           # (plane, line index): one thread
    args: dict

    def contains(self, other: "Span") -> bool:
        return (self.line == other.line and self.start <= other.start
                and other.end <= self.end)

    def overlaps(self, other: "Span") -> bool:
        return self.start < other.end and other.start < self.end


@dataclass
class Recording:
    spans: list = field(default_factory=list)

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def names(self) -> set:
        return {s.name for s in self.spans}

    def inside(self, outer: Span, name: str) -> list:
        return [s for s in self.named(name) if outer.contains(s)]


def read(out_dir) -> list:
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                spans.append(Span(e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns),
                                  (plane.name, i), dict(e.stats)))
    return spans


@contextmanager
def record(out_dir):
    rec = Recording()
    with session(out_dir):
        yield rec
    rec.spans = read(out_dir)
