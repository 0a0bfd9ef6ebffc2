"""CLI Train/Test entry points for the model zoo.

The reference ships one scopt ``Train``/``Test`` main per model
(models/lenet/Train.scala:35, models/inception/Train.scala,
models/resnet/TrainCIFAR10.scala, models/autoencoder/Train.scala,
models/rnn/Train.scala); this is the argparse equivalent as subcommands:

    python -m bigdl_tpu.models.run lenet-train  -f <mnist-dir> -b 64
    python -m bigdl_tpu.models.run lenet-test   -f <mnist-dir> --model lenet.bigdl
    python -m bigdl_tpu.models.run vgg-train    -b 128 --dataset cifar-synth
    python -m bigdl_tpu.models.run resnet-train -b 128 --depth 20
    python -m bigdl_tpu.models.run autoencoder-train -f <mnist-dir>

When no data folder is given a deterministic synthetic dataset is used so
every main runs self-contained (the reference requires downloaded MNIST /
CIFAR; synthetic keeps the path exercisable in CI).
"""

import argparse
import os
import sys

import numpy as np


def _mnist(folder, n=2048):
    from bigdl_tpu.dataset import mnist
    if folder:
        base = os.path.join(folder, "train-images-idx3-ubyte")
        if os.path.exists(base) or os.path.exists(base + ".gz"):
            return (mnist.load_mnist(folder, train=True),
                    mnist.load_mnist(folder, train=False))
        print(f"[warn] no MNIST idx files under {folder}; "
              "falling back to synthetic data")
    x, y = mnist.synthetic_mnist(n)
    # held-out tail as the synthetic "test" split
    k = n - n // 4
    return (x[:k], y[:k]), (x[k:], y[k:])


def _synthetic_images(n, h, w, c, classes, seed=11):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, size=n)
    x = rng.normal(size=(n, h, w, c)).astype(np.float32)
    # class-dependent mean shift so accuracy can move off chance
    x += ((y[:, None, None, None] + 1) / classes).astype(np.float32)
    return x, y


def _to_dataset(x, y, batch):
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    return array_dataset(x, y) >> SampleToMiniBatch(batch)


def _build_optimizer(args, model, train_ds, val_ds, criterion, method,
                     val_methods, strategy_kw=None):
    import bigdl_tpu.nn as nn  # noqa: F401  (registers layers for load)
    from bigdl_tpu.optim import Optimizer, Trigger
    from bigdl_tpu.utils.engine import Engine

    Engine.init()
    if getattr(args, "num_workers", 0):
        # async input pipeline (docs/performance.md, Input pipeline):
        # transform workers + bounded queue in front of the driver loop
        train_ds = train_ds.prefetch(num_workers=args.num_workers,
                                     queue_depth=args.queue_depth)
    route = strategy_kw or {"distributed": args.distributed}
    opt = Optimizer(model=model, dataset=train_ds, criterion=criterion,
                    optim_method=method, **route)
    opt.set_end_when(Trigger.max_epoch(args.max_epoch)
                     if args.max_iteration is None
                     else Trigger.max_iteration(args.max_iteration))
    if getattr(args, "sync_every", 1) != 1:
        opt.set_sync_every(args.sync_every)
    if val_ds is not None and val_methods:
        opt.set_validation(Trigger.every_epoch(), val_ds, val_methods)
    if args.checkpoint:
        opt.set_checkpoint(args.checkpoint, Trigger.every_epoch())
    if args.summary_dir:
        from bigdl_tpu.visualization import TrainSummary
        opt.set_train_summary(TrainSummary(args.summary_dir, args.app_name))
    return opt


def _common_flags(p, default_epochs=5):
    p.add_argument("-f", "--folder", default=None,
                   help="data folder (synthetic data when absent)")
    p.add_argument("-b", "--batchSize", type=int, default=64, dest="batch")
    p.add_argument("--learningRate", type=float, default=0.05, dest="lr")
    p.add_argument("--maxEpoch", type=int, default=default_epochs,
                   dest="max_epoch")
    p.add_argument("--maxIteration", type=int, default=None,
                   dest="max_iteration")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--summaryDir", default=None, dest="summary_dir")
    p.add_argument("--appName", default="bigdl_tpu", dest="app_name")
    p.add_argument("--distributed", action="store_true",
                   help="DistriOptimizer over the device mesh")
    p.add_argument("--model", default=None,
                   help="snapshot to load (resume / test)")
    p.add_argument("--synthN", type=int, default=2048, dest="synth_n")
    p.add_argument("--numWorkers", type=int, default=0, dest="num_workers",
                   help="prefetch transform workers (0 = synchronous)")
    p.add_argument("--queueDepth", type=int, default=4, dest="queue_depth",
                   help="prefetch queue depth (batches held ahead)")
    p.add_argument("--syncEvery", type=int, default=1, dest="sync_every",
                   help="block on the device loss every k-th step only")


def cmd_lenet_train(args):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.utils import serializer

    (xtr, ytr), (xte, yte) = _mnist(args.folder, args.synth_n)
    model = serializer.load_module(args.model) if args.model else LeNet5()
    opt = _build_optimizer(
        args, model, _to_dataset(xtr, ytr, args.batch),
        _to_dataset(xte, yte, args.batch), nn.ClassNLLCriterion(),
        optim.SGD(learning_rate=args.lr, momentum=0.9, dampening=0.0),
        [optim.Top1Accuracy()])
    opt.optimize()
    if args.checkpoint:
        serializer.save_module(model, os.path.join(args.checkpoint, "lenet.bigdl"))


def cmd_lenet_test(args):
    from bigdl_tpu import optim
    from bigdl_tpu.models.lenet import LeNet5
    from bigdl_tpu.optim.local_optimizer import validate
    from bigdl_tpu.utils import serializer

    import jax

    _, (xte, yte) = _mnist(args.folder, args.synth_n)
    model = serializer.load_module(args.model) if args.model else LeNet5()
    model.build(jax.ShapeDtypeStruct(xte[: args.batch].shape, xte.dtype))
    results = validate(model, model.parameters()[0], model.state(),
                       _to_dataset(xte, yte, args.batch),
                       [optim.Top1Accuracy(), optim.Top5Accuracy()])
    for r in results:
        print(r)


def cmd_vgg_train(args):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.models.vgg import VggForCifar10

    x, y = _synthetic_images(args.synth_n, 32, 32, 3, 10)
    holdout = max(1, min(256, len(x) // 4))
    model = VggForCifar10()
    opt = _build_optimizer(
        args, model, _to_dataset(x[:-holdout], y[:-holdout], args.batch),
        _to_dataset(x[-holdout:], y[-holdout:], args.batch), nn.ClassNLLCriterion(),
        optim.SGD(learning_rate=args.lr, momentum=0.9, dampening=0.0,
                  weight_decay=5e-4),
        [optim.Top1Accuracy()])
    opt.optimize()


def cmd_resnet_train(args):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.models.resnet import ResNetCifar

    x, y = _synthetic_images(args.synth_n, 32, 32, 3, 10)
    holdout = max(1, min(256, len(x) // 4))
    model = ResNetCifar(depth=args.depth)
    opt = _build_optimizer(
        args, model, _to_dataset(x[:-holdout], y[:-holdout], args.batch),
        _to_dataset(x[-holdout:], y[-holdout:], args.batch),
        nn.CrossEntropyCriterion(),
        optim.SGD(learning_rate=args.lr, momentum=0.9, dampening=0.0,
                  weight_decay=1e-4, nesterov=True),
        [optim.Top1Accuracy()])
    opt.optimize()


def _validate_remat_policy(args):
    """Fail fast on an unknown --rematPolicy NAME -- before any data
    prep or device init, with the list of valid jax.checkpoint_policies
    names (nn.resolve_checkpoint_policy), instead of an opaque
    AttributeError at first apply."""
    policy = getattr(args, "remat_policy", None)
    if policy is not None:
        from bigdl_tpu.nn import resolve_checkpoint_policy
        resolve_checkpoint_policy(policy)
    return policy


def cmd_resnet_imagenet_train(args):
    """The published ResNet-50/ImageNet recipe (reference:
    models/resnet/README.md:131-149 + TrainImageNet.scala): global batch
    8192, 90 epochs, 5-epoch linear warmup 0.1 -> 3.2, then 0.1x decay at
    epochs 30/60/80, SGD momentum 0.9, weight decay 1e-4.  Data: a folder
    of Hadoop SequenceFiles (--folder, the reference's ImageNet prep) or an
    ImageFolder tree; synthetic stand-in otherwise (the recipe itself --
    schedule, batch, epochs -- is exactly the published one either way)."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.models.resnet import ResNet

    n_train = 1281167
    steps_per_epoch = max(int(np.ceil(n_train / args.batch)), 1)
    warmup_epochs = 5
    base_lr, max_lr = args.lr, args.max_lr
    warmup_iteration = steps_per_epoch * warmup_epochs
    delta = (max_lr - base_lr) / warmup_iteration

    if args.folder and any(f.endswith(".seq")
                           for f in os.listdir(args.folder)):
        import io

        from PIL import Image

        from bigdl_tpu.dataset.seq_file import read_byte_records

        recs = read_byte_records(args.folder, class_num=1000)
        x = np.stack([
            np.asarray(Image.open(io.BytesIO(b)).convert("RGB")
                       .resize((224, 224)), np.float32) / 255.0
            for b, _ in recs])
        y = np.asarray([int(l) - 1 for _, l in recs], np.int32)
        n_train = len(x)
        steps_per_epoch = max(int(np.ceil(n_train / args.batch)), 1)
        warmup_iteration = steps_per_epoch * warmup_epochs
        delta = (max_lr - base_lr) / max(warmup_iteration, 1)
    elif args.folder:
        from bigdl_tpu.dataset.image_folder import find_images, decode_image

        items, _ = find_images(args.folder)
        x = np.stack([decode_image(p, (224, 224)) for p, _ in items])
        y = np.asarray([label for _, label in items], np.int32)
    else:
        x, y = _synthetic_images(max(args.synth_n // 4, args.batch * 2),
                                 224, 224, 3, 1000)

    model = ResNet(depth=50, class_num=1000, remat=args.remat,
                   stem_s2d=args.s2d,
                   remat_policy=_validate_remat_policy(args))
    method = optim.SGD(
        learning_rate=base_lr, momentum=0.9, dampening=0.0,
        weight_decay=1e-4,
        learning_rate_schedule=optim.EpochDecayWithWarmUp(
            warmup_iteration, delta, steps_per_epoch))
    if args.fused:
        # one flat-vector parameter update kernel (docs/performance.md)
        method = optim.Fused(method)
    opt = _build_optimizer(
        args, model, _to_dataset(x, y, args.batch), None,
        nn.CrossEntropyCriterion(), method, [optim.Top1Accuracy()])
    opt.optimize()


def cmd_inception_train(args):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.models.inception import (InceptionV1NoAuxClassifier,
                                            InceptionV2)

    x, y = _synthetic_images(max(args.synth_n // 8, args.batch * 2),
                             224, 224, 3, args.classes)
    model = (InceptionV2(args.classes) if args.version == "v2"
             else InceptionV1NoAuxClassifier(args.classes))
    opt = _build_optimizer(
        args, model, _to_dataset(x, y, args.batch), None,
        nn.ClassNLLCriterion(),
        optim.SGD(learning_rate=args.lr, momentum=0.9, dampening=0.0), [])
    opt.optimize()


def cmd_autoencoder_train(args):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.dataset import SampleToMiniBatch, array_dataset
    from bigdl_tpu.models.rnn import Autoencoder

    (xtr, _), _ = _mnist(args.folder, args.synth_n)
    flat = xtr.reshape(len(xtr), -1)
    ds = array_dataset(xtr, flat) >> SampleToMiniBatch(args.batch)
    opt = _build_optimizer(args, Autoencoder(32), ds, None,
                           nn.MSECriterion(),
                           optim.Adam(learning_rate=args.lr), [])
    opt.optimize()


def cmd_rnn_train(args):
    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim

    from bigdl_tpu.models.rnn import SimpleRNN

    rng = np.random.default_rng(3)
    vocab, seq = args.vocab, args.seq_len
    tokens = rng.integers(0, vocab, size=(args.synth_n, seq + 1))
    x, y = tokens[:, :-1], tokens[:, 1:]
    model = SimpleRNN(vocab, 40, vocab)
    opt = _build_optimizer(
        args, model, _to_dataset(x, y, args.batch), None,
        nn.TimeDistributedCriterion(nn.ClassNLLCriterion()),
        optim.SGD(learning_rate=args.lr), [])
    opt.optimize()


def cmd_transformer_train(args):
    """Transformer LM on a synthetic next-token corpus, single-device or
    sequence-parallel over a mesh (the long-context flagship; no reference
    analogue -- SURVEY.md §5 lists long-context as greenfield)."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.models.transformer import synthetic_corpus, transformer_lm

    vocab, seq = args.vocab, args.seq_len
    x, y = synthetic_corpus(args.synth_n, seq, vocab)
    remat_policy = _validate_remat_policy(args)
    #: --scanLayers auto|on|off -> None|True|False (transformer_lm's
    #: auto scans the deep configs; docs/performance.md)
    scan = {"auto": None, "on": True, "off": False}[args.scan_layers]
    # Pallas blockwise CE on TPU for big vocabs; plain formulation
    # elsewhere (ops/cross_entropy.py)
    crit = nn.TimeDistributedCriterion(nn.FusedSoftmaxCrossEntropyCriterion())

    if args.sp > 1 and args.pp > 1:
        raise ValueError("pick ONE of --sp / --pp (compose them in code "
                         "via parallel.pp_tp_shardings on a 3-D mesh)")
    if args.sp > 1 or args.pp > 1:
        if scan is True:
            raise ValueError(
                "--scanLayers on is incompatible with --sp/--pp: the "
                "model-parallel engines address per-block params "
                "(pp re-stacks blocks by STAGE); train scan-compiled "
                "models single-device or data-parallel")
        if args.pp > 1 and remat_policy is not None:
            # the pp engine re-implements the block forward per stage
            # (parallel/pp.py) and never runs TransformerLM.apply's
            # checkpoint wrapper -- silently accepting the flag would
            # "apply" a policy that changes nothing
            raise ValueError(
                "--rematPolicy has no effect under --pp: the pipeline "
                "engine drives the blocks directly and bypasses the "
                "model's remat wrapper; drop the flag (sp and "
                "single-device/dp paths honor it)")
        from bigdl_tpu.utils.engine import Engine

        from bigdl_tpu.models.transformer import CONFIGS

        deg = args.sp if args.sp > 1 else args.pp
        n_dev = jax.device_count()
        data_deg = n_dev // deg
        layers = CONFIGS[args.size][2]
        problems = []
        if n_dev % deg:
            problems.append(f"device count {n_dev} % degree {deg} != 0")
        if args.sp > 1 and seq % args.sp:
            problems.append(f"--seq-len {seq} % sp {args.sp} != 0")
        if args.pp > 1 and layers % args.pp:
            problems.append(f"--size {args.size} has {layers} "
                            f"blocks, not divisible into {args.pp} stages")
        if args.pp > 1 and args.batch % args.pp:
            problems.append(f"--batchSize {args.batch} % {args.pp} "
                            f"microbatches != 0")
        if (args.pp > 1 and args.batch % args.pp == 0
                and data_deg and (args.batch // args.pp) % data_deg):
            problems.append(f"microbatch {args.batch // args.pp} % "
                            f"data-parallel degree {data_deg} != 0")
        if data_deg and args.batch % data_deg:
            problems.append(f"--batchSize {args.batch} % data-parallel "
                            f"degree {data_deg} != 0")
        if problems:
            raise ValueError("model-parallel shape requirements: "
                             + "; ".join(problems))
        axis = "seq" if args.sp > 1 else "pipe"
        mesh = Engine.build_mesh((data_deg, deg), ("data", axis))
        model = transformer_lm(args.size, vocab, max_len=seq,
                               seq_axis_name="seq" if args.sp > 1 else None,
                               scan_layers=False,
                               remat_policy=remat_policy)
        strategy_kw = {"strategy": "sp" if args.sp > 1 else "pp",
                       "mesh": mesh}
        if args.pp > 1:
            strategy_kw.update(n_microbatches=args.pp,
                               schedule=args.pp_schedule)
        # full batches only: shard_map needs the batch axis divisible
        n_full = (len(x) // args.batch) * args.batch
        if n_full == 0:
            raise ValueError(f"--synthN {len(x)} < --batchSize {args.batch}")
        x, y = x[:n_full], y[:n_full]
        opt = _build_optimizer(args, model, _to_dataset(x, y, args.batch),
                               None, crit,
                               optim.Adam(learning_rate=args.lr), [],
                               strategy_kw=strategy_kw)
        opt.optimize()
        return

    model = transformer_lm(args.size, vocab, max_len=seq, scan_layers=scan,
                           remat_policy=remat_policy)
    opt = _build_optimizer(args, model, _to_dataset(x, y, args.batch), None,
                           crit, optim.Adam(learning_rate=args.lr), [])
    opt.optimize()


def main(argv=None):
    # progress must be visible out of the box (epoch/iteration/loss lines
    # come through logging.INFO); jax/XLA noise goes to bigdl.log via the
    # LoggerFilter analogue
    import logging

    from bigdl_tpu.utils.logger_filter import redirect_spark_info_logs
    logging.basicConfig(
        level=os.environ.get("BIGDL_LOG_LEVEL", "INFO").upper(),
        format="%(asctime)s %(levelname)-5s %(message)s")
    redirect_spark_info_logs()
    parser = argparse.ArgumentParser(prog="bigdl_tpu.models.run")
    sub = parser.add_subparsers(dest="command", required=True)

    specs = {
        "lenet-train": (cmd_lenet_train, 5, []),
        "lenet-test": (cmd_lenet_test, 1, []),
        "vgg-train": (cmd_vgg_train, 2, []),
        "resnet-train": (cmd_resnet_train, 2,
                         [("--depth", dict(type=int, default=20))]),
        "resnet-imagenet-train": (
            cmd_resnet_imagenet_train, 90,
            [("--maxLr", dict(type=float, default=3.2, dest="max_lr")),
             ("--fused", dict(action="store_true",
                              help="flat fused optimizer update")),
             ("--remat", dict(action="store_true",
                              help="rematerialise residual blocks")),
             ("--rematPolicy", dict(default=None, dest="remat_policy",
                                    metavar="NAME",
                                    help="jax.checkpoint_policies name for "
                                         "the block remat wrappers (e.g. "
                                         "dots_saveable, nothing_saveable; "
                                         "implies --remat)")),
             ("--s2d", dict(action="store_true",
                            help="space-to-depth 7x7 stem"))]),
        "inception-train": (cmd_inception_train, 1,
                            [("--version", dict(default="v1",
                                                choices=["v1", "v2"])),
                             ("--classes", dict(type=int, default=100))]),
        "autoencoder-train": (cmd_autoencoder_train, 2, []),
        "rnn-train": (cmd_rnn_train, 2,
                      [("--vocab", dict(type=int, default=100)),
                       ("--seq-len", dict(type=int, default=20,
                                          dest="seq_len"))]),
        "transformer-train": (
            cmd_transformer_train, 1,
            [("--vocab", dict(type=int, default=256)),
             ("--seq-len", dict(type=int, default=64, dest="seq_len")),
             ("--size", dict(default="tiny",
                             choices=["tiny", "small", "medium", "large"])),
             ("--sp", dict(type=int, default=1,
                           help="sequence-parallel degree (ring attention "
                                "over a data x seq mesh)")),
             ("--pp", dict(type=int, default=1,
                           help="pipeline-parallel stages (data x pipe "
                                "mesh; microbatches = stages)")),
             ("--pp-schedule", dict(default="gpipe",
                                    choices=["gpipe", "1f1b"],
                                    dest="pp_schedule")),
             ("--scanLayers", dict(default="auto",
                                   choices=["auto", "on", "off"],
                                   dest="scan_layers",
                                   help="compile the block stack as one "
                                        "lax.scan (auto: on for "
                                        "medium/large; incompatible with "
                                        "--sp/--pp)")),
             ("--rematPolicy", dict(default=None, dest="remat_policy",
                                    metavar="NAME",
                                    help="jax.checkpoint_policies name "
                                         "applied per transformer block "
                                         "(e.g. dots_saveable, "
                                         "nothing_saveable)"))]),
    }
    for name, (fn, epochs, extra) in specs.items():
        p = sub.add_parser(name)
        _common_flags(p, default_epochs=epochs)
        for flag, kw in extra:
            p.add_argument(flag, **kw)
        p.set_defaults(fn=fn)
        if name == "resnet-imagenet-train":
            # recipe defaults (models/resnet/README.md:131-149)
            p.set_defaults(lr=0.1)
        if name == "transformer-train":
            p.set_defaults(lr=1e-3)      # Adam-scale default

    args = parser.parse_args(argv)
    from bigdl_tpu.utils.config import (compilation_cache_note,
                                        enable_compilation_cache)
    # every invocation activates the cache (JAX_COMPILATION_CACHE_DIR, or
    # the checkout's fixed default) and logs the warm/cold note, so cache
    # reuse across runs/legs is always visible; a telemetry-carrying run
    # additionally stamps the same status on its JSONL header
    enable_compilation_cache()
    logging.getLogger("bigdl_tpu").info(compilation_cache_note())
    args.fn(args)


if __name__ == "__main__":
    main(sys.argv[1:])
