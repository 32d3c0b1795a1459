"""Read a ``.xplane.pb`` written by ``jax.profiler`` with nothing but JAX.

What a TPU trace holds (looked at by hand on a v5e, PR 23): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
run of a compiled program, named ``jit_<fn>(<hash>)``), ``XLA Ops`` (one
event per executed HLO instruction, named by the instruction's text,
``%name.N = type opcode(...)``) and ``Async XLA Ops`` (start-to-done spans
of asynchronous copies and collectives); and a plane ``/host:CPU`` whose
``python`` line carries ``jax.profiler.TraceAnnotation`` spans.  Device and
host stamps are on one clock to within a millisecond or two.
"""

import glob
import os
import re
from dataclasses import dataclass, field

SPAN_PREFIX = "bench:"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([\w.\-]+?)(?:\.\d+)? = ")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")


@dataclass
class Event:
    name: str      # the instruction's name without its number
    text: str      # the whole event name as the profiler wrote it
    start: float   # seconds
    duration: float

    @property
    def end(self):
        return self.start + self.duration


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)        # chip -> [Event]
    async_ops: dict = field(default_factory=dict)  # chip -> [Event]
    modules: dict = field(default_factory=dict)    # chip -> [Event]
    spans: list = field(default_factory=list)      # harness spans, host

    @property
    def chips(self):
        return sorted(self.ops)


def op_name(text):
    m = _OP_NAME.match(text)
    return m.group(1) if m else text.split("(")[0].strip()


def opcode(text):
    """The HLO opcode of an instruction's text, '' where there is none."""
    head = text.split(" = ", 1)
    m = _OPCODE.search(head[1] if len(head) == 2 else text)
    return m.group(1) if m else ""


def _events(line, named=True):
    return [Event(op_name(e.name) if named else e.name, e.name,
                  e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def read(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    trace.ops[chip] = _events(line)
                elif line.name == "Async XLA Ops":
                    trace.async_ops[chip] = _events(line)
                elif line.name == "XLA Modules":
                    trace.modules[chip] = _events(line, named=False)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        trace.spans.append(Event(
                            e.name[len(SPAN_PREFIX):], e.name,
                            e.start_ns * 1e-9, e.duration_ns * 1e-9))
    trace.spans.sort(key=lambda e: e.start)
    return trace
