"""ResNet CIFAR-10 training recipe (models/resnet/Train.scala:46-99 —
SGD lr 0.1, wd 1e-4, momentum 0.9, nesterov, EpochDecay(cifar10Decay:
x0.1 at epochs 81 and 122), batch 448, 165 epochs; models/resnet/README
BASELINE config 3's CIFAR variant).

    python -m bigdl_tpu.models.resnet.train -f /path/to/cifar10 --depth 20
    python -m bigdl_tpu.models.resnet.train --synthetic 256 -e 1
"""
from __future__ import annotations


def cifar10_decay(epoch: int) -> float:
    """resnet/Train.scala:34 cifar10Decay."""
    if epoch >= 122:
        return 2.0
    if epoch >= 81:
        return 1.0
    return 0.0


def imagenet_decay(epoch: int) -> float:
    """fb.resnet step schedule: x0.1 every 30 epochs."""
    return float(epoch // 30)


def _train_imagenet(args, nn, ResNet):
    """ResNet-50 ImageNet recipe: threaded ImageFolder feed with
    ColorJitter + Lighting on by default (dataset/image/ColorJitter.scala,
    Lighting.scala), SGD momentum 0.9 nesterov, x0.1 every 30 epochs."""
    from bigdl_tpu.models._cli import (arrays_to_dataset, load_model_or,
                                       wire_optimizer)
    from bigdl_tpu.optim import (EpochDecay, LocalOptimizer, SGD,
                                 Top1Accuracy, Top5Accuracy)

    bs = args.batchSize or 256
    # dataset-dependent default; an explicitly invalid depth still fails
    # fast inside ResNet()
    depth = args.depth if args.depth is not None else 50
    val_ds = None
    if args.synthetic:
        import numpy as np
        rng = np.random.RandomState(0)
        imgs = rng.rand(args.synthetic, 3, 224, 224).astype(np.float32)
        lbls = rng.randint(1, args.classNum + 1,
                           args.synthetic).astype(np.float32)
        ds = arrays_to_dataset(imgs, lbls, bs)
    else:
        from bigdl_tpu.dataset import ImageFolderDataSet
        ds = ImageFolderDataSet(args.folder, batch_size=bs, crop=224,
                                scale=256, color_jitter=args.colorJitter,
                                lighting=args.lighting)
        if args.valFolder:
            val_ds = ImageFolderDataSet(args.valFolder, batch_size=bs,
                                        crop=224, scale=256)
    model = load_model_or(
        args, lambda: ResNet(args.classNum, depth=depth,
                             dataset="ImageNet"))
    optim = SGD(learning_rate=args.learningRate or 0.1,
                learning_rate_decay=0.0, weight_decay=args.weightDecay,
                momentum=0.9, dampening=0.0, nesterov=args.nesterov,
                learning_rate_schedule=EpochDecay(imagenet_decay))
    opt = LocalOptimizer(model, ds, nn.CrossEntropyCriterion(),
                         batch_size=bs)
    wire_optimizer(opt, args, optim, val_ds=val_ds,
                   val_methods=[Top1Accuracy(), Top5Accuracy()],
                   default_epochs=90)
    opt.optimize()
    print(f"final loss: {opt.driver_state['Loss']:.4f}")
    return model


def main(argv=None):
    import argparse

    from bigdl_tpu.models._cli import (
        arrays_to_dataset, base_parser, cifar10_arrays, load_model_or,
        wire_optimizer)

    ap = base_parser("Train ResNet on CIFAR-10 / ImageNet")
    ap.add_argument("--depth", type=int, default=None,
                    help="default: 20 (cifar10) / 50 (imagenet)")
    ap.add_argument("--weightDecay", type=float, default=1e-4)
    ap.add_argument("--nesterov", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--dataset", choices=("cifar10", "imagenet"),
                    default="cifar10")
    ap.add_argument("--classNum", type=int, default=1000)
    ap.add_argument("--colorJitter", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="ImageNet only: random b/c/s (ColorJitter.scala)")
    ap.add_argument("--lighting", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="ImageNet only: PCA noise (Lighting.scala)")
    ap.add_argument("--valFolder", default=None,
                    help="ImageNet only: val folder for per-epoch "
                         "Top1/Top5")
    args = ap.parse_args(argv)

    import bigdl_tpu.nn as nn
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.optim import (EpochDecay, LocalOptimizer, Loss, SGD,
                                 Top1Accuracy, Top5Accuracy)

    if args.dataset == "imagenet":
        return _train_imagenet(args, nn, ResNet)

    bs = args.batchSize or 448
    tr = cifar10_arrays(args.folder, True, args.synthetic)
    va = cifar10_arrays(args.folder, False, args.synthetic or 0)
    model = load_model_or(
        args, lambda: ResNet(10, depth=args.depth or 20,
                             dataset="CIFAR10"))
    optim = SGD(learning_rate=args.learningRate or 0.1,
                learning_rate_decay=0.0, weight_decay=args.weightDecay,
                momentum=0.9, dampening=0.0, nesterov=args.nesterov,
                learning_rate_schedule=EpochDecay(cifar10_decay))
    opt = LocalOptimizer(model, arrays_to_dataset(*tr, bs),
                         nn.CrossEntropyCriterion(), batch_size=bs)
    wire_optimizer(opt, args, optim,
                   val_ds=arrays_to_dataset(*va, bs),
                   val_methods=[Top1Accuracy(), Top5Accuracy(), Loss()],
                   default_epochs=165)
    opt.optimize()
    print(f"final loss: {opt.driver_state['Loss']:.4f}")
    return model


if __name__ == "__main__":
    from bigdl_tpu.utils.engine import enable_compile_cache

    enable_compile_cache()
    main()
