"""Generate OPS_LEDGER.md: every NNVM-registered reference op mapped to
its status in this framework (SURVEY.md §7 op-mapping ledger).

Statuses:
- implemented       exact name (or known alias) in the op registry
- numpy-frontend    _npi_/_np_ op whose public numpy name exists in mx.np
- subsumed-by-AD    _backward_* kernels: jax.vjp derives every gradient,
                    so per-op backward registrations have no equivalent
- subsumed-by-XLA   fusion/cast/layout plumbing XLA performs itself
                    (amp_cast, amp_multicast, _FusedOp*, identity plumb)
- absent            no equivalent yet (the honest gap list)

Run from the repo root:  python tools/gen_ops_ledger.py
"""
import os
import re
import sys
from collections import Counter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = "/root/reference"


def reference_ops():
    names = set()
    pat = re.compile(r"(?:NNVM_REGISTER_OP|MXNET_REGISTER_OP_PROPERTY)\s*\(\s*([A-Za-z0-9_.]+)\s*[,)]")
    for root, _, files in os.walk(os.path.join(REF, "src", "operator")):
        for f in files:
            if not f.endswith((".cc", ".cu", ".h")):
                continue
            try:
                text = open(os.path.join(root, f), errors="ignore").read()
            except OSError:
                continue
            names.update(pat.findall(text))
    return sorted(names)


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    import mxnet_tpu as mx
    from mxnet_tpu.ops.registry import _REGISTRY

    registry = set(_REGISTRY)
    np_names = set(dir(mx.np)) | set(dir(mx.np.random)) | \
        set(dir(mx.np.linalg))
    npx_names = set(dir(mx.npx))

    NA_MARKERS = ("mkldnn", "_sg_", "tvm", "_dgl_", "TensorRT",
                  "_Native", "_NDArray", "CuDNN", "_CrossDeviceCopy")

    # capabilities that live as frontend functions rather than registry
    # entries (they take Python callables or sparse NDArray objects,
    # which the dense registry boundary cannot express)
    FRONTEND_IMPL = {
        "_foreach": "ndarray.contrib.foreach",
        "_while_loop": "ndarray.contrib.while_loop",
        "_cond": "ndarray.contrib.cond",
        "cast_storage": "ndarray.sparse.cast_storage",
        "_sparse_retain": "ndarray.sparse.retain",
    }

    def classify(op):
        if op == "name":   # macro-definition grep artifact
            return "not-applicable"
        if op in FRONTEND_IMPL:
            return "implemented"
        if op in registry:
            return "implemented"
        if "backward" in op.lower():
            return "subsumed-by-AD"
        if any(m in op for m in NA_MARKERS):
            # backend-specific kernels (oneDNN/TVM/TensorRT/DGL) with no
            # TPU meaning; XLA owns fusion and device copies
            return "not-applicable"
        if op.startswith(("_npi_", "_np_")):
            base = op[5:] if op.startswith("_npi_") else op[4:]
            cands = [base, base.replace("_scalar", ""),
                     base[1:].replace("_scalar", "")]  # r-variants
            for cand in cands:
                if cand in np_names or cand in npx_names \
                        or cand in registry:
                    return "numpy-frontend"
            return "absent"
        if op.startswith("amp_") or op.startswith("_FusedOp") or \
                op in ("_identity_with_attr_like_rhs", "_copy",
                       "_copyto"):
            return "subsumed-by-XLA"
        # strip leading underscore / contrib and retry registry
        for cand in (op.lstrip("_"), op.replace("_contrib_", ""),
                     op.lower()):
            if cand in registry:
                return "implemented"
        return "absent"

    ops = reference_ops()
    rows = [(op, classify(op)) for op in ops]
    counts = Counter(s for _, s in rows)

    out = [
        "# OPS LEDGER — reference operator coverage",
        "",
        "Generated by `tools/gen_ops_ledger.py`. Every op registered in",
        "the reference's `src/operator/` (NNVM_REGISTER_OP /",
        "MXNET_REGISTER_OP_PROPERTY) mapped to its status here.",
        "",
        "| Status | Count |",
        "|---|---|",
    ]
    for s in ("implemented", "numpy-frontend", "subsumed-by-AD",
              "subsumed-by-XLA", "not-applicable", "absent"):
        out.append(f"| {s} | {counts.get(s, 0)} |")
    out.append(f"| **total** | {len(rows)} |")
    out += ["", "Status meanings: see the generator's docstring. The",
            "`absent` rows are the auditable gap list.", ""]
    for status in ("implemented", "numpy-frontend", "subsumed-by-AD",
                   "subsumed-by-XLA", "not-applicable", "absent"):
        members = [op for op, s in rows if s == status]
        out.append(f"## {status} ({len(members)})")
        out.append("")
        for i in range(0, len(members), 6):
            out.append("`" + "` `".join(members[i:i + 6]) + "`")
        out.append("")

    path = os.path.join(REPO, "OPS_LEDGER.md")
    open(path, "w").write("\n".join(out))
    print(f"wrote {path}: {dict(counts)} of {len(rows)}")


if __name__ == "__main__":
    main()
