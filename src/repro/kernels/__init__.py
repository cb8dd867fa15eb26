# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.


def tpu_compiler_params(dimension_semantics: tuple):
    """Pallas-TPU CompilerParams.  Raises if pallas.tpu is unavailable;
    callers that must run on CPU wrap this in try/except."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
