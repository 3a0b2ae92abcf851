"""The program's own compile or cache-load seconds (``compile_s`` of
obs/xstats) summed over the runners built in set-up. Layer: simulation
set-up. Source: program span (the program times its own compile or
load). Reads ``record["executables"]``; nothing to read where the
record has none or no runner was timed."""


def read(trace, record):
    times = [r["compile_s"] for r in record.get("executables") or []
             if r.get("compile_s") is not None]
    return sum(times) if times else None
