"""A ``jax.profiler`` session for tests: what the program's spans look
like in the profiler's own trace (``dkt:<name>`` on ``/host:CPU``, the
span's args as event stats)."""

import glob
import os

import jax


class Profiled:
    """``with Profiled(tmp_path) as p: ...`` then ``p.spans``: the
    ``dkt:`` events of the session as ``{"name", "start", "end",
    "stats"}``, by start time."""

    def __init__(self, log_dir):
        self.log_dir = str(log_dir)
        self.spans = []

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(
            self.log_dir, "**", "*.xplane.pb"), recursive=True),
            key=os.path.getmtime)
        data = jax.profiler.ProfileData.from_file(paths[-1])
        for plane in data.planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("dkt:"):
                        self.spans.append({
                            "name": ev.name, "start": ev.start_ns,
                            "end": ev.start_ns + ev.duration_ns,
                            "stats": dict(ev.stats)})
        self.spans.sort(key=lambda s: (s["start"], -s["end"]))
        return False

    def named(self, name):
        return [s for s in self.spans if s["name"] == "dkt:" + name]

    def parent(self, span):
        """The shortest other span that holds ``span``, or ``None``."""
        holders = [s for s in self.spans if s is not span
                   and s["start"] <= span["start"]
                   and span["end"] <= s["end"]]
        return min(holders, key=lambda s: s["end"] - s["start"],
                   default=None)
