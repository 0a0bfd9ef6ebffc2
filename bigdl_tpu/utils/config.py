"""Flag/config tier: ``BIGDL_*`` environment variables.

Reference: the ``-Dbigdl.*`` JVM system-property tier (SURVEY.md section 5
"Config / flag system": bigdl.engineType utils/Engine.scala:45,210;
bigdl.localMode / bigdl.coreNumber :158-187; bigdl.failure.retryTimes
optim/DistriOptimizer.scala:862-908; bigdl.Parameter.syncPoolSize
parameters/AllReduceParameter.scala:36).  JVM properties become env vars:
``-Dbigdl.failure.retryTimes=5`` -> ``BIGDL_FAILURE_RETRY_TIMES=5``.
"""

import os


def _get(name, default, cast):
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return cast(raw)
    except ValueError:
        raise ValueError(f"invalid {name}={raw!r}")


def engine_type():
    """Engine selector (reference: bigdl.engineType picks MklBlas/MklDnn;
    ConversionUtils.convert routes through the IR accordingly).  Values:
    'xla' (default -- direct modules ARE the xla engine), 'ir' (lift to
    IR and lower back through the xla mapping: exercises the engine
    seam), 'ir-quantized' (IR + int8 MXU engine)."""
    return os.environ.get("BIGDL_ENGINE_TYPE", "xla")


def local_mode():
    return _get("BIGDL_LOCAL_MODE", False, lambda s: s.lower() == "true")


def core_number():
    return _get("BIGDL_CORE_NUMBER", None, int)


def failure_retry_times():
    """Reference: bigdl.failure.retryTimes (default 5) — bound on the
    optimizer's restore-from-checkpoint retry loop."""
    return _get("BIGDL_FAILURE_RETRY_TIMES", 5, int)


def check_singleton():
    return _get("BIGDL_CHECK_SINGLETON", False, lambda s: s.lower() == "true")


def log_file():
    """Reference: LoggerFilter redirect path (bigdl.utils.LoggerFilter
    defaults to ./bigdl.log)."""
    return os.environ.get("BIGDL_LOG_FILE", None)


def redirect_spark_info_logs(path=None):
    """LoggerFilter.redirectSparkInfoLogs equivalent — delegating alias;
    the implementation lives in :mod:`bigdl_tpu.utils.logger_filter`."""
    from bigdl_tpu.utils.logger_filter import redirect_spark_info_logs
    return redirect_spark_info_logs(log_file=path or log_file())


#: the compile cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: one
#: fixed, git-ignored directory at the root of the checkout (the path is
#: part of the cache's key, so a directory that moves never hits)
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and this sets nothing; where it is not, the cache goes to
    ``DEFAULT_COMPILATION_CACHE_DIR``."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILATION_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def compilation_cache_status():
    """``{"dir", "entries", "warm"}`` for the active compilation cache,
    or ``None`` when no cache dir is configured.  The ONE place the
    entry counting lives -- the log note below and the telemetry
    header both consume this, so they cannot disagree.  Sample it at
    run START: a lazily-taken count sees the run's own first compiles
    and misreports cold as warm."""
    import jax

    d = jax.config.jax_compilation_cache_dir
    if not d:
        return None
    try:
        n = len(os.listdir(d)) if os.path.isdir(d) else 0
    except OSError:
        n = 0
    return {"dir": d, "entries": n, "warm": n > 0}


def compilation_cache_note():
    """One-line warm/cold note for logs and the telemetry header:
    whether the active compilation cache already holds compiled
    programs (repeat runs skip the big XLA compiles) or starts cold."""
    status = compilation_cache_status()
    if status is None:
        return "compilation cache: disabled"
    n = status["entries"]
    return (f"compilation cache at {status['dir']}: {n} cached programs "
            f"({'warm -- repeat compiles will hit' if n else 'cold'})")
