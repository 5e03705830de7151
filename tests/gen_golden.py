"""Regenerate the golden parity files (tests/data/golden_*.json).

Run manually: `python tests/gen_golden.py` — only when a DELIBERATE
behavior change lands; commit the diff with an explanation."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lightgbm_tpu.utils.env import cleaned_cpu_env  # noqa: E402

# golden files are frozen on the CPU backend (one device), like the
# suite that checks them
os.environ.update(cleaned_cpu_env(os.environ, 1))

import lightgbm_tpu as lgb  # noqa: E402
from golden_common import GOLDEN_CASES, make_case_data, \
    model_fingerprint  # noqa: E402


def main():
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data")
    os.makedirs(out_dir, exist_ok=True)
    for name, case in GOLDEN_CASES.items():
        X, y = make_case_data(case)
        kw = {}
        if case.get("categorical"):
            kw["categorical_feature"] = case["categorical"]
        bst = lgb.train(dict(case["params"]),
                        lgb.Dataset(X, label=y, **kw),
                        num_boost_round=case["rounds"])
        fp = model_fingerprint(bst, X)
        path = os.path.join(out_dir, f"golden_{name}.json")
        with open(path, "w") as f:
            json.dump(fp, f, indent=1)
        # also freeze the full model text for the round-trip golden
        bst.save_model(os.path.join(out_dir, f"golden_{name}.model.txt"))
        print(f"wrote {path} ({len(fp['trees'])} trees)")


if __name__ == "__main__":
    main()
