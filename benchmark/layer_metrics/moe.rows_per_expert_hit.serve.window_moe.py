"""Routed pairs computed over the held experts hit, per layer-step, over
the run, for a Command-A+-shaped decoder that holds a share of its
experts: the program's counter ``mxtpu_llm_moe_routed_pairs_total`` (the
pairs on experts this chip holds) over ``mxtpu_llm_moe_experts_hit_total``
(all label children), as ``moe.rows_per_expert_hit.serve.gdn_mla_moe``
reads them. It is how many rows one read of an expert's weights serves,
the quantity by which the cut stands to the deployment it is one chip of
(PERF.md section 4). A program without the series, or a configuration
without window layers, gives nothing to read."""


def series(name):
    from mxnet_tpu.observability import get_registry
    metric = get_registry().get(name)
    return [] if metric is None else metric.children()


def read(seen):
    model = seen["config"]["model"]
    if not seen.get("traced") or "sliding_window" not in model:
        return None
    pairs = sum(c.value for c in series("mxtpu_llm_moe_routed_pairs_total"))
    hit = sum(c.value for c in series("mxtpu_llm_moe_experts_hit_total"))
    if not pairs or not hit:
        return None
    return pairs / hit
