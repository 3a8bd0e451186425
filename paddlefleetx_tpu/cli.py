"""Console entry points (``pfx-train`` etc., pyproject [project.scripts]).

The ``tools/*.py`` scripts (reference layout ``tools/train.py:37-67``,
``tools/auto.py:37-60``, ``tools/eval.py:33-53``,
``tools/export.py:32-49``, ``tools/inference.py:37-59``) delegate
here, so the repo-checkout and pip-installed surfaces run the same
code.
"""

from __future__ import annotations

import os


def maybe_virtual_cpu_mesh() -> None:
    """PFX_CPU_DEVICES=N: run any topology on an N-device virtual CPU
    mesh (podless correctness runs and rehearsals)."""
    if os.environ.get("PFX_CPU_DEVICES"):
        from .parallel.mesh import cpu_mesh_env
        cpu_mesh_env(int(os.environ["PFX_CPU_DEVICES"]))


def maybe_force_telemetry(cfg) -> None:
    """PFX_TELEMETRY=1 turns structured telemetry (flight recorder,
    dispatch counters, HBM watermarks) on for this run without a
    config edit — the path a preemption-prone fleet job or a one-off
    triage run takes. 0/off forces it off over the config."""
    env = os.environ.get("PFX_TELEMETRY")
    if env is None:
        return
    on = env.strip().lower() in ("1", "true", "yes", "on")
    cfg.setdefault("Telemetry", {})
    cfg.Telemetry["enable"] = on


def build_trainer(argv=None, devices=None):
    """Config parse -> mesh -> module -> Engine -> dataloaders: what
    ``train_main`` does before ``Engine.fit``. Returns ``(cfg,
    engine, train_loader, valid_loader)``. ``devices`` pins the mesh
    to a subset of the host's devices (default: all of them)."""
    maybe_virtual_cpu_mesh()
    from .core import Engine
    from .data import build_dataloader
    from .models import build_module
    from .parallel.mesh import process_data_loader_count, \
        process_data_rank
    from .utils import env
    from .utils.config import get_config, parse_args

    args = parse_args(argv)
    env.init_dist_env()
    nranks = len(devices) if devices is not None else None
    cfg = get_config(args.config, overrides=args.override, show=True,
                     nranks=nranks)
    maybe_force_telemetry(cfg)

    module = build_module(cfg)
    engine = Engine(cfg, module, mode="train", devices=devices)

    data_world = process_data_loader_count(engine.mesh)
    rank = process_data_rank(engine.mesh)
    seed = cfg.Global.get("seed")
    train_loader = build_dataloader(cfg.Data, "Train",
                                    num_replicas=data_world, rank=rank,
                                    seed=seed)
    valid_loader = build_dataloader(cfg.Data, "Eval",
                                    num_replicas=data_world, rank=rank,
                                    seed=seed)
    if train_loader is not None:
        # per-process slice of the global batch
        train_loader.batch_sampler.batch_size = \
            cfg.Global.global_batch_size // data_world
    if valid_loader is not None:
        valid_loader.batch_sampler.batch_size = \
            cfg.Global.global_batch_size // data_world
    return cfg, engine, train_loader, valid_loader


def train_main(argv=None, devices=None):
    """``tools/train.py`` entry: config parse -> mesh -> module ->
    dataloaders -> ``Engine.fit`` (reference ``tools/train.py:37-67``
    call stack, SURVEY.md section 3.1). Returns the fitted Engine."""
    from .utils.log import logger

    cfg, engine, train_loader, valid_loader = build_trainer(
        argv, devices=devices)
    engine.fit(epoch=cfg.Engine.get("num_train_epochs", 1),
               train_data_loader=train_loader,
               valid_data_loader=valid_loader)
    if engine._recorder is not None:
        logger.info("flight record at %s", engine._recorder.path)
    logger.info("training finished")
    return engine


def train_script(argv=None):
    """Console wrapper: setuptools runs ``sys.exit(main())``, so the
    script entry must not return train_main's Engine."""
    train_main(argv)


def auto_main(argv=None):
    """GSPMD is the auto engine — the auto schema runs the same
    trainer (SURVEY §7 design stance)."""
    train_main(argv)


def eval_main(argv=None):
    """``tools/eval.py`` entry: offline WikiText/LAMBADA evaluation
    through ``GPTEvalModule`` (reference ``tools/eval.py:33-53``);
    returns the metrics dict."""
    maybe_virtual_cpu_mesh()
    from .core import Engine
    from .data import build_dataloader
    from .models import build_module
    from .utils.config import get_config, parse_args

    args = parse_args(argv)
    cfg = get_config(args.config, overrides=args.override, show=True)
    cfg.Model.module = "GPTEvalModule"
    module = build_module(cfg)
    engine = Engine(cfg, module, mode="eval")
    loader = build_dataloader(cfg.Data, "Eval")
    engine.evaluate(epoch=0, valid_data_loader=loader)
    return module.metrics


def export_main(argv=None):
    """``tools/export.py`` entry: jit + ``jax.export`` of the
    inference forward into a re-partitionable artifact (replaces the
    reference's ``to_static`` + per-rank dirs, ``tools/export.py:
    32-49``)."""
    maybe_virtual_cpu_mesh()
    from .core import Engine
    from .models import build_module
    from .utils import env
    from .utils.config import get_config, parse_args
    from .utils.log import logger

    args = parse_args(argv)
    env.init_dist_env()
    cfg = get_config(args.config, overrides=args.override, show=True)
    module = build_module(cfg)
    engine = Engine(cfg, module, mode="export")
    if cfg.Engine.save_load.get("ckpt_dir"):
        engine.load()
    path = engine.export()
    logger.info("export finished: %s", path)
    return path


def eval_script(argv=None):
    """Console wrapper: setuptools runs ``sys.exit(main())``, so the
    script entry must not return eval_main's metrics dict."""
    eval_main(argv)


def export_script(argv=None):
    export_main(argv)


def inference_main(argv=None):
    """``tools/inference.py`` entry: load the exported artifact and
    run batch prediction (reference ``tools/inference.py:37-59``)."""
    maybe_virtual_cpu_mesh()
    import numpy as np

    from .core import Engine
    from .data import build_dataloader
    from .models import build_module
    from .utils import env
    from .utils.config import get_config, parse_args
    from .utils.log import logger

    args = parse_args(argv)
    env.init_dist_env()
    cfg = get_config(args.config, overrides=args.override, show=False)
    module = build_module(cfg)
    engine = Engine(cfg, module, mode="inference")

    loader = build_dataloader(cfg.Data, "Test")
    for i, batch in enumerate(loader):
        outs = engine.inference([np.asarray(x) for x in batch])
        logger.info("batch %d -> %s", i,
                    {k: v.shape for k, v in outs.items()})
