"""The share of the pages an all-full layout would hold that a cache with
window layers does not, over the traced steps: 1 less the one-layer pages
the step's sequences held (``kv_pages_held`` of the program's
``mxtpu.llm.step`` spans, matched to the traced steps by their ``rows`` as
``kernel.moe_experts_roofline.serve`` matches them: ``work_window_moe.py``
reads its function from its file) over what they would hold if every layer
kept all of its pages (``kv_pages_full_layout``), both summed over the
steps. It is what the window layers give back for other sequences to take.
A program without those attributes gives nothing to read. In per cent."""
import importlib


def read(seen):
    if not seen.get("traced") or not seen["traced"]["steps"]:
        return None
    work = importlib.import_module("benchmark.work_window_moe")
    steps = [s["attrs"] for s in work.traced_step_spans(seen)
             if "kv_pages_full_layout" in s["attrs"]]
    full = sum(a["kv_pages_full_layout"] for a in steps)
    if not full:
        return None
    return 100.0 * (1.0 - sum(a["kv_pages_held"] for a in steps) / full)
