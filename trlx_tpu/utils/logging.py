"""Library-wide logging with per-process rank awareness.

Equivalent in behavior to the reference's logging subsystem
(``trlx/utils/logging.py:47-340``): a package-level verbosity controlled by the
``TRLX_TPU_VERBOSITY`` env var, loggers that prefix messages with the JAX
process index, and a ``ranks=`` kwarg to restrict a record to specific hosts.
"""

import logging
import os
import sys
import threading
from typing import List, Optional

_lock = threading.Lock()
_default_handler: Optional[logging.Handler] = None

CRITICAL = logging.CRITICAL
ERROR = logging.ERROR
WARNING = logging.WARNING
INFO = logging.INFO
DEBUG = logging.DEBUG
NOTSET = logging.NOTSET

_log_levels = {
    "critical": CRITICAL,
    "error": ERROR,
    "warning": WARNING,
    "info": INFO,
    "debug": DEBUG,
}

_default_log_level = logging.INFO


def _get_default_level() -> int:
    env = os.getenv("TRLX_TPU_VERBOSITY", None)
    if env:
        if env.lower() in _log_levels:
            return _log_levels[env.lower()]
        logging.getLogger().warning(
            f"Unknown TRLX_TPU_VERBOSITY={env}, must be one of {list(_log_levels)}"
        )
    return _default_log_level


def _root_name() -> str:
    return __name__.split(".")[0]  # "trlx_tpu"


def _configure_root():
    global _default_handler
    with _lock:
        if _default_handler:
            return
        _default_handler = logging.StreamHandler(sys.stdout)
        _default_handler.setFormatter(
            logging.Formatter(
                "[%(asctime)s] [%(levelname)s] [%(name)s] %(message)s",
                datefmt="%H:%M:%S",
            )
        )
        root = logging.getLogger(_root_name())
        root.addHandler(_default_handler)
        root.setLevel(_get_default_level())
        root.propagate = False


def _process_index() -> int:
    # Cheap: prefer env (set before jax.distributed init) over importing jax.
    for var in ("TRLX_TPU_PROCESS_ID", "JAX_PROCESS_INDEX", "RANK"):
        if var in os.environ:
            try:
                return int(os.environ[var])
            except ValueError:
                pass
    # Read the distributed-runtime state WITHOUT initializing a backend:
    # ``jax.process_index()`` would trigger backend init, and a process that
    # has initialized the backend holds the chip — a log prefix must never
    # touch the accelerator (the sweep CLI logs, then launches the trials
    # that need it).
    try:
        from jax._src import distributed

        pid = distributed.global_state.process_id
        return int(pid) if pid is not None else 0
    except Exception:
        return 0


class MultiProcessAdapter(logging.LoggerAdapter):
    """Logs only on selected processes; prefixes messages with the rank.

    ``logger.info(msg, ranks=[0])`` emits on process 0 only (default).
    ``ranks=[-1]`` emits everywhere.
    """

    def log(self, level, msg, *args, **kwargs):
        ranks = kwargs.pop("ranks", [0])
        idx = _process_index()
        if idx in ranks or -1 in ranks:
            if self.isEnabledFor(level):
                msg, kwargs = self.process(f"[RANK {idx}] {msg}", kwargs)
                self.logger.log(level, msg, *args, **kwargs)


def get_logger(name: Optional[str] = None) -> MultiProcessAdapter:
    """Return a rank-aware logger under the trlx_tpu namespace."""
    _configure_root()
    if name is None:
        name = _root_name()
    elif not name.startswith(_root_name()):
        name = f"{_root_name()}.{name}"
    return MultiProcessAdapter(logging.getLogger(name), {})


def get_verbosity() -> int:
    _configure_root()
    return logging.getLogger(_root_name()).getEffectiveLevel()


def set_verbosity(verbosity: int) -> None:
    _configure_root()
    logging.getLogger(_root_name()).setLevel(verbosity)


def set_verbosity_debug():
    set_verbosity(DEBUG)


def set_verbosity_info():
    set_verbosity(INFO)


def set_verbosity_warning():
    set_verbosity(WARNING)


def set_verbosity_error():
    set_verbosity(ERROR)


def enable_explicit_format() -> None:
    _configure_root()


def disable_progress_bars() -> bool:
    os.environ["TRLX_TPU_NO_TQDM"] = "1"
    return True


def progress_bars_disabled() -> bool:
    return os.environ.get("TRLX_TPU_NO_TQDM", "0") == "1"


def tqdm(*args, **kwargs):
    """Verbosity-aware progress bar (reference ``_tqdm_cls``,
    ``trlx/utils/logging.py:305-330``); honors ``TRLX_TPU_NO_TQDM``."""
    from tqdm import auto

    kwargs["disable"] = bool(kwargs.get("disable")) or progress_bars_disabled()
    return auto.tqdm(*args, **kwargs)
