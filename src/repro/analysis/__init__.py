from repro.analysis.hlo import (collective_bytes, count_aliased_args,
                                count_stablehlo_collectives,
                                parse_hlo_collectives)

# census / blocks / check import jax (and the kernels package) — they are
# reached as submodules (``repro.analysis.check``) so that this package,
# like the pure-AST lint layer, stays importable without jax.
__all__ = ["collective_bytes", "count_aliased_args",
           "count_stablehlo_collectives", "parse_hlo_collectives"]
