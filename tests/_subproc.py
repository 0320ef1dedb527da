"""Shared env for tests that spawn jax subprocesses on simulated devices."""
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def subprocess_env():
    """Inherit the environment (a bare env hangs jax backend probing on CPU
    containers), pinned to the CPU: the children fake host devices, and a
    child that reached for the TPU would fight its parent for the chip.
    Scripts set their own XLA_FLAGS."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO_ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env
