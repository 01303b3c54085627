"""What a metric reader is given: the run's configuration and model
family, calls, set-up, compile count and (in a traced run) its trace,
with the arithmetic several readers share. A reader is
``bench/metrics/<name>.py`` with ``read(ctx)``; it returns ``None`` where
it finds nothing to read."""
from __future__ import annotations

import dataclasses
import re
import types

from benchlib import spec
from benchlib import trace as trace_lib

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = spec.load_json(spec.BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


def padded_width(config: dict) -> int:
    """Elements of one node's flat parameter buffer: the model's
    parameters rounded up to whole 128-lane rows."""
    p = config["params"]
    return -(-p // 128) * 128


@dataclasses.dataclass
class Context:
    config: dict
    family: types.ModuleType    # families/<family>.py of the configuration
    traffic: dict
    cell: dict
    device: dict
    calls: list                 # [(start, end)] host clock, seconds
    setup_s: float
    compiles: int
    trace: trace_lib.Trace | None = None

    # -- the work -----------------------------------------------------------
    @property
    def variants(self) -> int:
        sweep = self.traffic.get("variants")
        return 1 if sweep is None else sweep["seeds"] * len(
            sweep["mobility"])

    @property
    def samples_per_round(self) -> int:
        f, t = self.config["fleet"], self.config["train"]
        return (self.variants * f["num_nodes"] * f["local_steps"]
                * t["batch_size"])

    @property
    def rounds(self) -> int:
        """Rounds of every call in the window."""
        return len(self.calls) * self.traffic["rounds_per_call"]

    @property
    def traced_rounds(self) -> int:
        """Rounds of the calls made under the profiler."""
        return len(self.call_spans()) * self.traffic["rounds_per_call"]

    def window(self) -> tuple:
        return self.calls[0][0], self.calls[-1][1]

    def kernel_shape(self) -> dict:
        f = self.config["fleet"]
        return {"nodes": f["num_nodes"], "width": padded_width(self.config),
                "degree": f.get("degree") or f["num_nodes"] - 1,
                "wire_bytes": {"f32": 4, "bf16": 2}[f["wire_dtype"]],
                "separate_wire": (self.traffic.get("faults") is not None
                                  or f["wire_dtype"] != "f32")}

    # -- the trace ----------------------------------------------------------
    def device_events(self) -> list:
        """Operations of each chip's plane, one list per chip."""
        return [ev for name, ev in sorted(self.trace.device.items())
                if DEVICE_PLANE.match(name) and ev]

    def call_spans(self) -> list:
        return [(s, e) for name, s, e in self.trace.spans
                if name == "bench.call"]

    def trace_window(self) -> tuple:
        spans = self.call_spans()
        return spans[0][0], spans[-1][1]

    def busy_ns(self) -> float:
        """Device-busy nanoseconds of the traced window, averaged over
        the chips."""
        lo, hi = self.trace_window()
        chips = self.device_events()
        if not chips:
            return 0.0
        return sum(trace_lib.busy(ev, lo, hi) for ev in chips) / len(chips)

    def busy_window(self) -> tuple:
        lo, hi = self.trace_window()
        return self.busy_ns() / 1e9, (hi - lo) / 1e9

    def breakdown(self) -> dict:
        lo, hi = self.trace_window()
        chips = self.device_events()
        ev = chips[0] if chips else []
        return {"device_ops": trace_lib.top_ops(ev, lo, hi),
                "idle_gaps": trace_lib.idle_by_span(ev, self.trace.spans,
                                                    lo, hi)}

    def kernel_ns(self, kernel: str) -> float:
        lo, hi = self.trace_window()
        return sum(trace_lib.kernel_time(ev, kernel, lo, hi)
                   for ev in self.device_events())

    def roofline(self, kernel: str, cost) -> float | None:
        """Least time the kernel's calls in the traced window could take
        on this chip, over the time its events took, in percent."""
        if self.trace is None:
            return None
        spent = self.kernel_ns(kernel)
        if spent <= 0:
            return None
        pk = peaks(self.device["kind"])
        flops, nbytes = cost(self.kernel_shape())
        least = max(flops / pk["bf16_flops_per_s"],
                    nbytes / pk["hbm_bytes_per_s"])
        calls = self.traced_rounds * self.variants
        return 100.0 * least * calls / (spent / 1e9)
