"""Seconds the serving engine's construction took: the
``serving.engine_init`` span's counter as the process has it now.  The
one engine of a process is built during set-up, so the counter does not
grow over the window, and ``obs["numbers"]`` (growth over the window)
cannot show it; ``ratio`` reads nothing else."""


def read(obs, params):
    from dmlc_tpu import telemetry

    serving = telemetry.counters_snapshot().get("serving", {})
    return serving.get("engine_init_secs")
