"""Programs that JAX built or loaded, from the runtime's own compile
event: seconds of them during set-up, or their count inside the window."""


def read(spec: dict, h):
    if spec["what"] == "setup_seconds":
        return sum(h.compiles.between(h.t0, h.setup_end))
    if spec["what"] == "window_count":
        return len(h.compiles.between(*h.window))
    raise ValueError(f"compiles: unknown quantity {spec['what']!r}")
