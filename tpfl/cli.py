"""tpfl command-line interface.

Parity with reference ``p2pfl/cli.py:65-238`` (Typer app with
``experiment list/run/help``), built on click. The reference's
``login/remote/launch`` commands are explicit not-implemented stubs
there (``cli.py:71-95``); here they are omitted entirely.
"""

from __future__ import annotations

import importlib
import os
import pkgutil
import subprocess
import sys

import click


@click.group()
def main() -> None:
    """tpfl — TPU-native peer-to-peer federated learning."""


@main.group()
def experiment() -> None:
    """Run bundled example experiments."""


def _discover_examples() -> dict[str, str]:
    import tpfl.examples as ex

    return {
        m.name: f"tpfl.examples.{m.name}"
        for m in pkgutil.iter_modules(ex.__path__)
        if not m.name.startswith("_")
    }


@experiment.command("list")
def list_experiments() -> None:
    """List bundled experiments (reference cli.py:102-130)."""
    for name in sorted(_discover_examples()):
        click.echo(name)


@experiment.command("help", context_settings={"ignore_unknown_options": True})
@click.argument("name")
def help_experiment(name: str) -> None:
    ex = _discover_examples()
    if name not in ex:
        raise click.ClickException(f"Unknown experiment '{name}'")
    mod = importlib.import_module(ex[name])
    click.echo(mod.__doc__ or "(no description)")


@experiment.command(
    "run", context_settings={"ignore_unknown_options": True}
)
@click.argument("name")
@click.option(
    "--profile",
    "profile_dir",
    metavar="DIR",
    default=None,
    help="wrap the run in a jax.profiler trace written to DIR "
    "(view with TensorBoard/xprof)",
)
@click.argument("args", nargs=-1, type=click.UNPROCESSED)
def run_experiment(
    name: str, profile_dir: "str | None", args: tuple[str, ...]
) -> None:
    """Run an example in a subprocess (reference cli.py:162-189)."""
    ex = _discover_examples()
    if name not in ex:
        raise click.ClickException(f"Unknown experiment '{name}'")
    env = dict(os.environ)
    if profile_dir:
        # The trace happens in the CHILD: hand the dir across as the
        # Settings env override (examples apply Settings.from_env()
        # after their profile), and the stage workflow wraps the
        # experiment in jax.profiler.start/stop_trace.
        env["TPFL_PROFILING_TRACE_DIR"] = profile_dir
    rc = subprocess.call([sys.executable, "-m", ex[name], *args], env=env)
    sys.exit(rc)


if __name__ == "__main__":
    main()
