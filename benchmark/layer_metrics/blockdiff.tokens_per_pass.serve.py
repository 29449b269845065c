"""Positions revealed for each row pass, over the run, of a model that
generates by diffusion over blocks: the program's counter
``mxtpu_llm_blockdiff_revealed_total`` over its
``mxtpu_llm_blockdiff_passes_total`` (denoising and settling passes; all
label children). At a block of 4 revealed in 4 passes and settled in a
fifth it reads 0.8: the number a change to the passes moves (fewer passes
a block, settling fused with the next block's first pass). A program
without the series gives nothing to read."""


def series(name):
    from mxnet_tpu.observability import get_registry
    metric = get_registry().get(name)
    return [] if metric is None else metric.children()


def read(seen):
    if not seen.get("traced"):
        return None
    revealed = sum(c.value
                   for c in series("mxtpu_llm_blockdiff_revealed_total"))
    passes = sum(c.value for c in series("mxtpu_llm_blockdiff_passes_total"))
    if not revealed or not passes:
        return None
    return revealed / passes
