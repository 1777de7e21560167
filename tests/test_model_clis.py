"""Per-model Train/Test entry points (reference: models/*/Train.scala,
Test.scala mains) — each recipe must run end-to-end with --synthetic."""
import numpy as np
import pytest



def test_lenet_train_cli(tmp_path):
    from bigdl_tpu.models.lenet.train import main
    model = main(["--synthetic", "64", "-b", "16", "--maxIterations", "6",
                  "--checkpoint", str(tmp_path)])
    assert model is not None
    assert any(tmp_path.iterdir())  # checkpoint written


def test_lenet_train_cli_graph_model():
    from bigdl_tpu.models.lenet.train import main
    assert main(["--synthetic", "32", "-b", "16", "--maxIterations",
                 "2", "-g"]) is not None


def test_lenet_test_cli(capsys):
    from bigdl_tpu.models.lenet.test import main
    results = main(["--synthetic", "48", "-b", "16"])
    out = capsys.readouterr().out
    assert "Top1Accuracy" in out and results


def test_vgg_train_cli():
    from bigdl_tpu.models.vgg.train import main
    assert main(["--synthetic", "32", "-b", "16",
                 "--maxIterations", "2"]) is not None


def test_resnet_train_cli():
    from bigdl_tpu.models.resnet.train import main
    assert main(["--synthetic", "32", "-b", "16", "--depth", "20",
                 "--maxIterations", "2"]) is not None


def test_resnet_cifar10_decay_schedule():
    from bigdl_tpu.models.resnet.train import cifar10_decay
    assert cifar10_decay(1) == 0.0
    assert cifar10_decay(81) == 1.0   # x0.1 (Train.scala:34)
    assert cifar10_decay(122) == 2.0  # x0.01


def test_inception_train_cli():
    from bigdl_tpu.models.inception.train import main
    assert main(["--synthetic", "8", "-b", "4", "--classNum", "10",
                 "--maxIterations", "2"]) is not None


def test_rnn_train_cli():
    from bigdl_tpu.models.rnn.train import main
    assert main(["--synthetic", "800", "-b", "8", "--vocabSize", "30",
                 "--numSteps", "5", "--maxIterations", "3"]) is not None


def test_rnn_train_cli_ptb_from_text(tmp_path):
    p = tmp_path / "train.txt"
    p.write_text("the cat sat on the mat\n" * 40)
    from bigdl_tpu.models.rnn.train import main
    assert main(["-f", str(p), "--vocabSize", "20", "-b", "4",
                 "--numSteps", "4", "--maxIterations", "3",
                 "--ptb"]) is not None


def test_autoencoder_train_cli():
    from bigdl_tpu.models.autoencoder.train import main
    assert main(["--synthetic", "64", "-b", "32",
                 "--maxIterations", "2"]) is not None


def test_snapshot_resume_flow(tmp_path):
    """Train, snapshot with save_module, resume via --model
    (Train.scala:48-56 modelSnapshot pattern)."""
    from bigdl_tpu.models.lenet.train import main
    from bigdl_tpu.utils.serialization import save_module

    model = main(["--synthetic", "32", "-b", "16", "--maxIterations", "2"])
    snap = str(tmp_path / "lenet_snapshot")
    save_module(snap, model)
    model2 = main(["--synthetic", "32", "-b", "16", "--maxIterations", "1",
                   "--model", snap])
    assert model2 is not None


def test_lenet_test_cli_quantized(capsys):
    """--quantize evaluates the int8-rewritten model (ModelValidator's
    quantized path, example/loadmodel)."""
    from bigdl_tpu.models.lenet.test import main
    results = main(["--synthetic", "32", "-b", "16", "--quantize"])
    out = capsys.readouterr().out
    assert "Top1Accuracy" in out and results


def test_rnn_test_cli_evaluate(capsys):
    """Evaluate branch of models/rnn/Test.scala:55-90 — Loss over a
    TimeDistributed CrossEntropy, perplexity printed."""
    from bigdl_tpu.models.rnn.test import main
    results = main(["--synthetic", "400", "-b", "4", "--vocabSize", "30",
                    "--numSteps", "5"])
    out = capsys.readouterr().out
    assert "Loss" in out and "perplexity" in out and results


def test_rnn_test_cli_generate():
    """Generation branch (Test.scala:91-137) — each step appends one
    predicted token."""
    from bigdl_tpu.models.rnn.test import main
    gen = main(["--synthetic", "200", "-b", "4", "--vocabSize", "30",
                "--numSteps", "5", "--numOfWords", "3"])
    assert gen.shape[1] == 5 + 3


def test_rnn_test_cli_from_snapshot(tmp_path):
    """Trained snapshot round-trips into the test main (the reference's
    Module.load path, Test.scala:52)."""
    from bigdl_tpu.models.rnn.test import main as test_main
    from bigdl_tpu.models.rnn.train import main as train_main
    from bigdl_tpu.utils.serialization import save_module

    model = train_main(["--synthetic", "400", "-b", "4", "--vocabSize",
                        "30", "--numSteps", "5", "--maxIterations", "2"])
    snap = str(tmp_path / "rnn_snap")
    save_module(snap, model)
    results = test_main(["--synthetic", "200", "-b", "4", "--vocabSize",
                         "30", "--numSteps", "5", "--model", snap])
    assert "Loss" in results


def test_inception_test_cli(capsys):
    from bigdl_tpu.models.inception.test import main
    results = main(["--synthetic", "8", "-b", "4", "--classNum", "10"])
    out = capsys.readouterr().out
    assert "Top1Accuracy" in out and "Top5Accuracy" in out and results


def test_autoencoder_test_cli(capsys):
    from bigdl_tpu.models.autoencoder.test import main
    results = main(["--synthetic", "32", "-b", "16"])
    out = capsys.readouterr().out
    assert "Loss" in out and results


def test_rnn_dictionary_roundtrip(tmp_path):
    """Train saves the vocabulary; test reloads it so words keep their
    training-time indices (Train.scala:90 vocab.save / Test.scala:52
    Dictionary(folder))."""
    import os
    from bigdl_tpu.models.rnn.test import main as test_main
    from bigdl_tpu.models.rnn.train import main as train_main

    txt = tmp_path / "train.txt"
    txt.write_text("the cat sat on the mat\n" * 30)
    ck = tmp_path / "ck"
    train_main(["-f", str(txt), "--vocabSize", "20", "-b", "4",
                "--numSteps", "4", "--maxIterations", "2",
                "--checkpoint", str(ck)])
    dict_path = ck / "dictionary.json"
    assert dict_path.exists()
    results = test_main(["-f", str(txt), "-b", "4", "--numSteps", "4",
                         "--dictionary", str(dict_path)])
    assert "Loss" in results


def test_resnet_imagenet_train_cli():
    """ImageNet branch: ResNet-18 recipe with the fb.resnet step
    schedule; jitter/lighting flags are parsed (folder path wires them
    into ImageFolderDataSet)."""
    from bigdl_tpu.models.resnet.train import imagenet_decay, main
    assert imagenet_decay(29) == 0.0
    assert imagenet_decay(30) == 1.0
    assert imagenet_decay(60) == 2.0
    assert main(["--synthetic", "8", "-b", "4", "--dataset", "imagenet",
                 "--depth", "18", "--classNum", "10",
                 "--maxIterations", "2"]) is not None


def test_resnet_imagenet_with_val_folder(tmp_path):
    """ImageNet recipe wires a val ImageFolder for per-epoch Top1/Top5
    (Train.scala:100 valSet); tiny real-JPEG folders end to end."""
    import os
    from PIL import Image

    rng = np.random.RandomState(0)
    for split, per in (("train", 3), ("val", 2)):
        for cls in ("a", "b"):
            d = tmp_path / split / cls
            os.makedirs(d)
            for i in range(per):
                Image.fromarray(rng.randint(
                    0, 255, (240, 260, 3), np.uint8)).save(d / f"{i}.jpg")

    from bigdl_tpu.models.resnet.train import main
    m = main(["-f", str(tmp_path / "train"), "--dataset", "imagenet",
              "--depth", "18", "--classNum", "2", "-b", "2",
              "--valFolder", str(tmp_path / "val"),
              "--maxIterations", "3"])
    assert m is not None


def test_transformer_train_cli():
    # data parallelism absorbs all devices by default, so the batch must
    # divide by the device count (8 on the virtual test mesh)
    from bigdl_tpu.models.transformer.train import main
    model = main(["--synthetic", "600", "-b", "8", "--vocabSize", "30",
                  "--hiddenSize", "16", "--layers", "2", "--heads", "2",
                  "--seqLen", "8", "--maxIterations", "3"])
    assert model is not None


def test_transformer_train_cli_pp_tp():
    import jax
    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs 8 virtual devices")
    from bigdl_tpu.models.transformer.train import main
    model = main(["--synthetic", "600", "-b", "8", "--vocabSize", "32",
                  "--hiddenSize", "16", "--layers", "4", "--heads", "2",
                  "--seqLen", "8", "--pp", "2", "--tp", "2",
                  "--maxIterations", "3"])
    assert model is not None


def test_transformer_train_cli_sp_ring():
    import jax
    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs 8 virtual devices")
    from bigdl_tpu.models.transformer.train import main
    model = main(["--synthetic", "600", "-b", "4", "--vocabSize", "32",
                  "--hiddenSize", "16", "--layers", "2", "--heads", "4",
                  "--seqLen", "16", "--sp", "ring", "--spSize", "4",
                  "--maxIterations", "3"])
    assert model is not None


def test_transformer_test_cli_perplexity(capsys):
    from bigdl_tpu.models.transformer.test import main
    ppl = main(["--synthetic", "600", "-b", "4", "--vocabSize", "30",
                "--hiddenSize", "16", "--layers", "2", "--heads", "2",
                "--seqLen", "8"])
    out = capsys.readouterr().out
    assert "perplexity" in out and ppl > 0
