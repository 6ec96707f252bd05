import os

# Tests run single-device CPU (the dry-run alone forces 512 host devices,
# and only in its own subprocess — see src/repro/launch/dryrun.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

jax.config.update("jax_enable_x64", False)

# Identical programs re-jitted from fresh closures (every test builds its own
# env/step fns) hit this cache instead of recompiling — cuts the tier-1 wall
# clock severalfold, both within a run and across runs. The directory is the
# program's own rule: JAX_COMPILATION_CACHE_DIR, else the checkout's
# .jax_cache/.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@pytest.fixture(scope="session", autouse=True)
def _force_cpu():
    """Belt-and-braces: some modules re-touch jax config at import time."""
    jax.config.update("jax_platform_name", "cpu")
    yield


@pytest.fixture(scope="session")
def small_sizes():
    """Default scale for new tests: keep jit times in the tens of ms."""
    return dict(n_envs=4, rollout_len=8, ep_len=16, n_episodes=4,
                hidden=16, epochs=2)
