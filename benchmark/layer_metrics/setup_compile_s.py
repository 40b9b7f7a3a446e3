"""Layer "entry": seconds of set-up the backend spent COMPILING: JAX's
backend-compile events that followed no cache hit on their thread (a
miss, or the cache not consulted). 0 in a warm run. Source: the
program's set-up account."""

from benchmark import setup_account


def read(obs):
    return setup_account.phase(obs, "compile")
