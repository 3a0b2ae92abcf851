"""Set-up seconds of the harness's span around Simulation(...) plus the
first call of the round runner (a compile or a persistent-cache load;
run.py prints hit or miss on an earlier line). Layer: simulation
set-up. Source: host clock."""


def read(trace, record):
    return record["runner_compile_s"]
