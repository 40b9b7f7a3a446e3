"""Layer "entry": seconds of set-up spent taking programs FROM the
persistent compilation cache (retrieval, deserialisation, load onto the
device): JAX's backend-compile events that followed a cache hit on
their thread. 0 in a cold run. Source: the program's set-up account."""

from benchmark import setup_account


def read(obs):
    return setup_account.phase(obs, "load")
