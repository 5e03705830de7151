"""lightgbm_tpu — a TPU-native gradient-boosted decision tree framework.

A from-scratch re-design of LightGBM (reference: jchen9314/LightGBM) for TPU:
JAX/XLA/Pallas compute path, `jax.sharding` data-parallel tree learning over
ICI/DCN, with the LightGBM Python API reproduced verbatim
(`Dataset` / `Booster` / `train` / `cv` / sklearn estimators).
"""
import time as _time
_IMPORT_T0 = _time.perf_counter()   # the gauge `setup.import_s`, set last

from .basic import Dataset, LightGBMError, Sequence  # noqa: F401
from .utils.log import register_logger  # noqa: F401

__version__ = "0.3.0"  # keep in sync with pyproject.toml [project] version

__all__ = ["Dataset", "LightGBMError", "Sequence", "register_logger",
           "__version__"]

# Booster/engine/callback/sklearn land in later milestones of this round;
# each import is made unconditional as soon as the module exists.
import importlib.util as _ilu

if _ilu.find_spec(".booster", __package__) is not None:
    from .booster import Booster  # noqa: F401
    __all__.append("Booster")

if _ilu.find_spec(".engine", __package__) is not None:
    from .engine import CVBooster, cv, train  # noqa: F401
    __all__ += ["train", "cv", "CVBooster"]

if _ilu.find_spec(".callback", __package__) is not None:
    from .callback import (early_stopping, log_evaluation,  # noqa: F401
                           record_evaluation, reset_parameter)
    __all__ += ["early_stopping", "log_evaluation", "record_evaluation",
                "reset_parameter"]

if _ilu.find_spec(".sklearn", __package__) is not None:
    from .sklearn import (LGBMClassifier, LGBMModel,  # noqa: F401
                          LGBMRanker, LGBMRegressor)
    __all__ += ["LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"]

if _ilu.find_spec(".plotting", __package__) is not None:
    # matplotlib/graphviz are imported lazily inside each function, so the
    # re-export is safe without either installed (stock lightgbm exports
    # these at package level the same way)
    from .plotting import (create_tree_digraph,  # noqa: F401
                           plot_importance, plot_metric,
                           plot_split_value_histogram, plot_tree)
    __all__ += ["plot_importance", "plot_metric",
                "plot_split_value_histogram", "plot_tree",
                "create_tree_digraph"]

from .telemetry import REGISTRY as _REGISTRY  # noqa: E402

_REGISTRY.gauge("setup.import_s").set(_time.perf_counter() - _IMPORT_T0)
del _time, _IMPORT_T0, _REGISTRY
