"""How full the steps ran: the tokens the traced steps fed over the
packed lengths of the programs they took, in percent. A step pays the
matrix products, the norms and the head of its rung whatever it feeds, so
this is the share of that work done for a token. The sums of ``tokens``
and of ``t_bucket`` over the ring's ``mxtpu.llm.build`` spans, the spans
``engine.build_ms.serve`` takes."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    builds = [s["attrs"] for s in
              ps.named(ps.ring(seen), "mxtpu.llm.build")]
    rung = sum(a.get("t_bucket", 0) for a in builds)
    if not rung:
        return None
    return 100.0 * sum(a.get("tokens", 0) for a in builds) / rung
