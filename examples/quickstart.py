"""Quickstart: relational queries over matrix data with MatRel-JAX.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import Session

rng = np.random.default_rng(0)


def main():
    s = Session()

    # A sparse 2000×1000 feature matrix (1e-3 density)
    x = np.where(rng.uniform(size=(2000, 1000)) < 1e-3,
                 rng.normal(size=(2000, 1000)), 0).astype(np.float32)
    X = s.load(x, "X")

    # --- Code 1 from the paper: trace of a Gram matrix ---------------------
    tr = X.t().multiply(X).trace()
    print("== plan + rewrite for trace(XᵀX) ==")
    print(tr.explain())
    print("trace =", float(tr.to_numpy().ravel()[0]), "\n")

    # --- selection pushdown (Code 2) ----------------------------------------
    g11 = X.t().multiply(X).select("RID=1 AND CID=1")
    print("== σ_{RID=1∧CID=1}(XᵀX) becomes a vector inner product ==")
    print(g11.explain())
    print("G[1,1] =", float(g11.to_numpy().ravel()[0]), "\n")

    # --- joins (Codes 4, 5) ---------------------------------------------------
    a = np.where(rng.uniform(size=(512, 512)) < 5e-3,
                 rng.normal(size=(512, 512)), 0).astype(np.float32)
    b = np.where(rng.uniform(size=(512, 512)) < 5e-3,
                 rng.normal(size=(512, 512)), 0).astype(np.float32)
    A, B = s.load(a, "A"), s.load(b, "B")
    overlay = A.join(B, "RID=RID AND CID=CID", lambda x_, y_: x_ * y_)
    out = overlay.collect()
    print("direct overlay nnz:", int(np.asarray(out.nnz())))

    d2d = A.join(B, "RID=RID", lambda x_, y_: x_ * y_)
    t = d2d.collect()
    print(f"D2D join → order-{t.order} tensor, {t.nnz} matches")

    # a sparsity-inducing merge (f(0, y) = f(x, 0) = 0) joins only the
    # nonzero entries; a non-inducing one would also pair every zero cell
    v2v = A.join(B, "VAL=VAL", lambda x_, y_: x_ * y_)
    tv = v2v.collect()
    print(f"V2V (Bloom) join → order-{tv.order} tensor, {tv.nnz} matches")

    # --- relational cleaning (σ_rows≠NULL) -----------------------------------
    dirty = a.copy()
    dirty[::7] = 0.0
    D = s.load(dirty, "D")
    clean = D.select("rows != NULL").to_numpy()
    print(f"rows≠NULL: {dirty.shape[0]} → {clean.shape[0]} rows")


if __name__ == "__main__":
    main()
