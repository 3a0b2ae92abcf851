"""Driver-blocked output time per snapshot written: the program's own
RunStats ``io.exposed_s`` (all phases) summed over the traced window's
run_once calls, over their ``steps_written``. Layer: driver output
(driver.run_once, io/async_writer.py, io/stream.py, io/bplite.py).
Source: program counter (GS_TPU_STATS)."""


def read(trace, record):
    runs = record.get("io_runs") or []
    written = sum(r.get("steps_written", 0) for r in runs)
    if not written:
        return None
    exposed = sum(sum((r.get("exposed_s") or {}).values()) for r in runs)
    return 1e3 * exposed / written
