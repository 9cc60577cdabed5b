"""Rank-aware logging.

TPU-native analogue of the reference's ``deepspeed/utils/logging.py``
(`logger` + `log_dist` rank-filtered logging). Process identity comes from
``jax.process_index()`` instead of torch.distributed ranks.
"""

import functools
import logging
import os
import sys

LOG_FORMAT = "[%(asctime)s] [%(levelname)s] [%(name)s:%(lineno)d] %(message)s"


@functools.lru_cache(None)
def _make_logger(name: str = "deepspeed_tpu", level=logging.INFO):
    logger_ = logging.getLogger(name)
    logger_.setLevel(level)
    logger_.propagate = False
    handler = logging.StreamHandler(stream=sys.stdout)
    handler.setFormatter(logging.Formatter(LOG_FORMAT))
    logger_.addHandler(handler)
    return logger_


logger = _make_logger()


def _process_index() -> int:
    # Never INITIALISE a backend just to log: jax.process_index() takes the
    # chip, and a process that only orchestrates children (the autotuner
    # under process isolation, the launcher) must leave it free for them.
    # Ask JAX only once something else has brought the backend up; until
    # then the launcher's env contract answers.
    if "jax" in sys.modules:
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized():
            import jax
            return jax.process_index()
    return int(os.environ.get("PROCESS_ID", os.environ.get("RANK", "0")))


def log_dist(message: str, ranks=None, level=logging.INFO) -> None:
    """Log `message` only on the given process indices (None / [-1] = all)."""
    my_rank = _process_index()
    if ranks is None or -1 in ranks or my_rank in ranks:
        logger.log(level, f"[Rank {my_rank}] {message}")


def should_log_le(max_log_level_str: str) -> bool:
    levels = {
        "debug": logging.DEBUG,
        "info": logging.INFO,
        "warning": logging.WARNING,
        "error": logging.ERROR,
        "critical": logging.CRITICAL,
    }
    target = levels.get(max_log_level_str.lower())
    if target is None:
        raise ValueError(f"Invalid log level: {max_log_level_str}")
    return logger.getEffectiveLevel() <= target


def see_memory_usage(message: str, force: bool = False, ranks=(0,)) -> dict:
    """Device + host memory telemetry (reference runtime/utils.py
    ``see_memory_usage``: CUDA allocated/reserved + psutil RSS; here per-
    device HBM stats from the backend + host RSS/available). Returns the
    numbers and logs them rank-filtered."""
    import jax
    report = {"devices": [], "host": {}}
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        report["devices"].append({
            "device": str(d),
            "bytes_in_use": stats.get("bytes_in_use", 0),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
            "bytes_limit": stats.get("bytes_limit", 0),
        })
    try:
        import psutil
        vm = psutil.virtual_memory()
        p = psutil.Process()
        report["host"] = {"rss": p.memory_info().rss,
                          "available": vm.available, "percent": vm.percent}
    except ImportError:
        try:
            with open("/proc/self/status") as fh:
                for line in fh:
                    if line.startswith("VmRSS"):
                        report["host"]["rss"] = \
                            int(line.split()[1]) * 1024
        except OSError:
            pass
    dev = report["devices"][0] if report["devices"] else {}
    log_dist(
        f"{message} | HBM {dev.get('bytes_in_use', 0)/2**30:.2f}/"
        f"{dev.get('bytes_limit', 0)/2**30:.2f} GB "
        f"(peak {dev.get('peak_bytes_in_use', 0)/2**30:.2f}) | host RSS "
        f"{report['host'].get('rss', 0)/2**30:.2f} GB",
        ranks=list(ranks))
    return report
