"""Bundled runnable experiments (reference p2pfl/examples/)."""


def start_on_device() -> dict:
    """What every example's ``main`` does first: arm the ONE compile
    cache (``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache`` — ``profiling.compile_cache_dir``) and SAY
    which device the run is on. JAX falls back to the CPU with no more
    than a warning; an example that prints no platform cannot be told
    from one that lost its chip. In a ``jax.distributed`` world call it
    after joining (it queries the backend)."""
    from tpfl.management.profiling import ensure_compile_cache
    from tpfl.parallel.mesh import device_report

    cache = ensure_compile_cache()
    device = device_report()
    print(
        f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} | compile cache: {cache}",
        flush=True,
    )
    return device
