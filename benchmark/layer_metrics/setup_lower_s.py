"""Layer "entry": seconds of set-up spent LOWERING jaxprs to MLIR
modules — where every Pallas kernel is lowered to Mosaic — every
program of the process, outermost events only. The persistent cache is
keyed on the lowered module, so a warm process pays this too. Source:
the program's set-up account (``jax.monitoring``'s
``jaxpr_to_mlir_module_duration``)."""

from benchmark import setup_account


def read(obs):
    return setup_account.phase(obs, "lower")
