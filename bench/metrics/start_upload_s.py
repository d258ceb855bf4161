"""Mean seconds of the program's ``engine.upload`` span in each start of
the traced window: from the end of ``engine.init`` until the engine state
is on the device. Read from the spans the profiler recorded, on the device
trace's clock, so only where the trace has a device plane: without one
nothing went to a device. The run's output also gives the bytes and the
rate."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace_window
    ups = [s.dur_ns for s in run.trace.spans
           if s.name == "engine.upload" and lo <= s.start_ns <= hi]
    if not ups:
        return None
    secs = sum(ups) / len(ups) * 1e-9
    sizes = [s.attrs.get("bytes", 0) for s in run.spans
             if s.name == "engine.upload"]
    if sizes and secs > 0:
        nbytes = sum(sizes) / len(sizes)
        print(f"engine.upload: {nbytes:.0f} bytes in {secs:.6g} s, "
              f"{nbytes / secs / 1e9:.6g} GB/s", flush=True)
    return secs
