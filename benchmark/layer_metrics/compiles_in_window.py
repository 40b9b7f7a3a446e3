"""Layer "window dispatch": backend compiles that JAX's monitoring
counted during the measured window. Must be 0: every program was
compiled or loaded from the cache during the warm-up."""


def read(obs):
    return obs["compiles_in_window"]
