"""Host seconds of set-up in which some program was traced, lowered to
MLIR or compiled (or loaded from the compile cache): the union of JAX's
``/jax/core/compile/*_duration`` spans, which nest."""


def read(run):
    return run.setup["compile_s"]
