"""Where JAX keeps compiled programs between processes.

Every `pio train` / `pio batchpredict` / online-fold process is a fresh
interpreter, and the ALS train is one large program holding every bucket
shape of the capacity ladder: without a persistent cache each of them
pays the full compile. JAX reads `JAX_COMPILATION_CACHE_DIR` when it is
first imported, so the whole contract is one environment variable:

- set from outside → it is used as is, and nothing is set in code;
- unset → `<checkout>/.pio_store/jax_cache`. The path is part of the
  cache's key, so it is fixed: never a tempdir, a pid or a timestamp.

Call `configure()` at process entry, before anything imports jax. Child
processes inherit the variable through the environment.
"""

from __future__ import annotations

import os
from typing import MutableMapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> str:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".pio_store", "jax_cache")


def configure(env: Optional[MutableMapping[str, str]] = None) -> str:
    """Resolve the cache directory into `env` (default: the process
    environment) and return it."""
    env = os.environ if env is None else env
    return env.setdefault(ENV_VAR, default_dir())
