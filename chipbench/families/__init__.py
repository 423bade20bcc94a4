"""One module per architecture family, found by file name.

Everything in ``chipbench/`` that knows what a model is made of sits in
``families/<family>.py``; the runners, ``flops.py`` and the readers ask
that module and read no layer count, width or parameter name themselves.
A configuration file names its family with the key ``"family"``; without
the key it is ``"gpt2"``.  A family module answers (``gpt2.py`` has all
five; a family that only serves, or only trains, leaves the other out):

1. ``make_params(cfg, positions, seed)``: weights from ``--seed`` under
   the program's parameter names, made on the device in one jitted call;
2. ``serving_engine(params, cfg, registry, geometry)``: the system under
   test for serving, from the traffic file's ``engine`` geometry;
3. ``training_program(cfg, mix)``: builds the model into the Program the
   runner has opened (``program_guard``) and returns its loss variable;
   the recipe (remat, accumulation, mesh) stays in the runner;
4. the plain reference: ``logits(params, tokens, cfg)`` (serving) and
   ``greedy_loss(params, tokens, cfg)`` (training);
5. ``sizes(cfg)``: what shape-only code needs, as one dict of integers:
   ``d_model``; ``heads`` and ``head_dim``; ``vocab_rows``
   (rows of the vocabulary held); ``matmul_params`` (matmul parameters
   APPLIED per token: a weight used four times a token counts four
   times); ``kv_planes`` (K/V planes a token holds: layers x times each
   runs); ``attention_passes`` (attention applications per token).
"""

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT = "gpt2"
# what each runner takes from a family module
NEEDS = {"serve": ("make_params", "serving_engine", "logits", "sizes"),
         "train": ("make_params", "training_program", "greedy_loss",
                   "sizes")}


def load(name, directory=None):
    """The module ``<directory>/<name>.py``.  With no directory: the
    module of that name already loaded, else the one beside this file."""
    key = "chipbench.families." + name
    if directory is None and sys.modules.get(key) is not None:
        return sys.modules[key]
    path = os.path.join(directory or HERE, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"chipbench: no family {name!r}: {path} is not "
                         f"there (a configuration's \"family\" names a "
                         f"file of chipbench/families/)")
    spec = importlib.util.spec_from_file_location(key, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[key] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[key]
        raise
    return module


def of(cfg, runner=None):
    """The family module of configuration ``cfg``; with ``runner``
    (``"train"`` or ``"serve"``), one clear error where the family leaves
    out what that runner needs."""
    name = cfg.get("family", DEFAULT)
    family = load(name)
    missing = [f for f in NEEDS.get(runner, ()) if not hasattr(family, f)]
    if missing:
        raise SystemExit(
            f"chipbench: family {name!r} does not {runner}: "
            f"{family.__file__} has no {', '.join(missing)} "
            f"(configuration {cfg.get('name')!r})")
    return family


def sizes(cfg):
    return of(cfg).sizes(cfg)
