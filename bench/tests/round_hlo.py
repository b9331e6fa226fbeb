"""The optimized HLO of a small cell's ``train_round`` with its metadata and
stack-frame tables taken off: what the chip runs, whatever scopes the
program sets.  It is built from whichever program comes first on
``PYTHONPATH``, with the compile cache off, so two trees can be compared:

    PYTHONPATH=bench:bench/tests:<tree>/src JAX_PLATFORMS=cpu \\
        python3 bench/tests/round_hlo.py olmo1b-pd-p4 > <tree>.hlo

``--topology v5e:2x2`` compiles for a described TPU instead of the CPU's
devices; the ring's small copy on the CPU needs
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import argparse


def text(cell: str, topology: str | None = None) -> str:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    import faults
    from harness import scopes
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    devices = None
    if topology:
        from jax.experimental import topologies
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name=topology).devices
    compiled = scopes.lower(faults.tiny(cell), devices).compile()
    return scopes.stripped(compiled.as_text())


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--topology", help="a described TPU, e.g. v5e:2x2")
    args = ap.parse_args(argv)
    print(text(args.cell, args.topology))


if __name__ == "__main__":
    main()
