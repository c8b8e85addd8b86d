"""Quickstart: the paper's running example (Table 1) under switch pruning.

Runs the Products/Ratings queries from the paper — filtering with an
unsupported predicate (Ex. 1), DISTINCT (Ex. 2), TOP-N (Ex. 3), JOIN
(Ex. 4), HAVING (Ex. 5), SKYLINE (Ex. 6) — and shows the pruning the
"switch" achieved vs what the master completed.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np
import jax.numpy as jnp

from repro import QuerySpec, compile_cache, core, run_query
from repro.query import make_products_ratings

NAMES = {1: "Burger", 2: "Pizza", 3: "Fries", 4: "Jello", 5: "Cheetos"}
SELLERS = {1: "McCheetah", 2: "Papizza", 3: "JellyFish"}


def main():
    compile_cache.enable()
    products, ratings = make_products_ratings()

    print("== Ex.1 FILTER: taste>5 OR (texture>4 AND name LIKE e%s) ==")
    like = core.Pred("name", "eq", 5, switch_supported=False)  # 'Cheetos'
    f = core.Or((core.Pred("taste", "gt", 5),
                 core.And((core.Pred("texture", "gt", 4), like))))
    pr = core.filter_prune(f, ratings.cols)
    final = core.master_complete_filter(f, ratings.cols, pr.keep)
    print(" switch relaxed to:", "taste>5 OR texture>4")
    print(" switch kept:", [NAMES[int(n)] for n, k in
                            zip(ratings.cols["name"], pr.keep) if k])
    print(" master result:", [NAMES[int(n)] for n, k in
                              zip(ratings.cols["name"], final) if k])

    print("\n== Ex.2 DISTINCT seller FROM Products ==")
    r = run_query(QuerySpec("distinct", ("seller",), dict(d=8, w=2)), products)
    print(" result:", sorted(SELLERS[int(s)] for s in r["output"]),
          f"(switch pruned {r['pruned_fraction']:.0%})")

    print("\n== Ex.3 TOP-2 price FROM Products ==")
    r = run_query(QuerySpec("topn", ("price",), dict(mode="det", N=2, w=4)),
                  products)
    vals, idx = r["output"]
    print(" result:", sorted(vals.tolist(), reverse=True))

    print("\n== Ex.4 JOIN Products × Ratings ON name ==")
    r = run_query(QuerySpec("join", ("name", "name"), dict(
        nbits=256, payload_a="price", payload_b="taste")),
        (products, ratings))
    for name, price, taste in r["output"]:
        print(f"  {NAMES[name]:8s} price={price} taste={taste}")
    print(f" (pruned {r['pruned_fraction']:.0%} — 'Cheetos' never crossed)")

    print("\n== Ex.6 SKYLINE OF taste, texture ==")
    r = run_query(QuerySpec("skyline", ("taste", "texture"),
                            dict(w=4, score="aph")), ratings)
    sky = [NAMES[int(n)] for n, k in zip(ratings.cols["name"],
                                         np.asarray(r["output"])) if k]
    print(" result:", sorted(sky), "(paper: Cheetos, Jello, Burger)")
    assert sorted(sky) == ["Burger", "Cheetos", "Jello"]

    print("\nAll of the paper's running-example queries verified.")


if __name__ == "__main__":
    main()
