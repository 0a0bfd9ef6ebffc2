"""AlexNet / Inception-v2 and the CLI Train mains (models/run.py, perf.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from bigdl_tpu.models.alexnet import AlexNet, AlexNetOWT
from bigdl_tpu.models.inception import InceptionV2


class TestAlexNet:
    @pytest.mark.slow      # ISSUE-13 re-tier (~8s); tier-1 sibling:
    def test_alexnet_grouped_forward(self):   # owt param-count below
        # original AlexNet: grouped conv2/4/5, LRN; input 227
        y = AlexNet(10, has_dropout=False).forward(jnp.zeros((1, 227, 227, 3)))
        assert y.shape == (1, 10)

    def test_alexnet_owt_param_count(self):
        import jax
        m = AlexNetOWT(1000, has_dropout=False)
        m.build(jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))
        n = sum(p.size for p in jax.tree.leaves(m.parameters()[0]))
        # torchvision alexnet (OWT): 61.1M params
        assert abs(n - 61.1e6) / 61.1e6 < 0.01, n


class TestInceptionV2:
    @pytest.mark.slow
    def test_forward_shape(self):
        # slow tier: a ~26s full 224x224 InceptionV2 compile; the
        # (already slow-marked) inception-train v2 CLI smoke covers the
        # same build path
        y = InceptionV2(7).forward(jnp.zeros((1, 224, 224, 3)))
        assert y.shape == (1, 7)


class TestCliMains:
    def test_lenet_train_and_test_main(self, tmp_path):
        from bigdl_tpu.models import run
        run.main(["lenet-train", "--synthN", "128", "-b", "32",
                  "--maxIteration", "2"])
        run.main(["lenet-test", "--synthN", "128", "-b", "32"])

    def test_compilation_cache_placed_from_outside(self, tmp_path,
                                                   monkeypatch):
        """Where JAX_COMPILATION_CACHE_DIR is set the program uses that
        directory and sets no other in code; where it is not, the cache
        is the one fixed, git-ignored directory inside the checkout.
        Every CLI run turns the cache on and the note helper reports its
        state."""
        import os

        import jax

        from bigdl_tpu.models import run
        from bigdl_tpu.utils import config

        was = jax.config.jax_compilation_cache_dir
        outside = str(tmp_path / "xla_cache")
        try:
            # the variable wins: nothing is set in code (JAX reads the
            # variable itself at start-up; stand in for that here)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
            jax.config.update("jax_compilation_cache_dir", outside)
            run.main(["lenet-train", "--synthN", "64", "-b", "32",
                      "--maxIteration", "1"])
            assert jax.config.jax_compilation_cache_dir == outside
            assert config.enable_compilation_cache() == outside
            assert outside in config.compilation_cache_note()
            # unset: the fixed directory inside the checkout
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            inside = config.enable_compilation_cache()
            assert inside == config.DEFAULT_COMPILATION_CACHE_DIR
            repo = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            assert os.path.dirname(inside) == repo
            assert jax.config.jax_compilation_cache_dir == inside
            with open(os.path.join(repo, ".gitignore")) as f:
                assert os.path.basename(inside) + "/" in f.read().split()
        finally:
            # tmp_path dies with the test; later tests must never compile
            # against a deleted cache dir
            jax.config.update("jax_compilation_cache_dir", was)

    def test_perf_driver(self):
        from bigdl_tpu.models import perf
        rate = perf.run_perf("lenet", batch=16, iterations=2)
        assert rate > 0

    @pytest.mark.slow
    def test_perf_driver_token_models(self):
        """The LM rows (the reference's SimpleRNN throughput; transformer
        flagship) run through the same fused-step perf harness.  Slow
        tier (~27s of compiles); test_perf_driver pins the harness."""
        from bigdl_tpu.models import perf
        assert perf.run_perf("simplernn", batch=4, iterations=2) > 0
        assert perf.run_perf("lstm_lm", batch=2, iterations=2) > 0
        assert perf.run_perf("transformer", batch=2, iterations=2) > 0


@pytest.mark.slow
class TestRunCommandsSmoke:
    """Every models/run.py subcommand executes end-to-end on tiny synthetic
    workloads (the reference exercises each Train.scala main)."""

    def _run(self, *argv):
        from bigdl_tpu.models import run

        run.main(list(argv) + ["--synthN", "64", "-b", "32",
                               "--maxIteration", "2"])

    def test_vgg_train(self):
        self._run("vgg-train")

    def test_resnet_train(self):
        self._run("resnet-train", "--depth", "8")

    def test_inception_train(self):
        self._run("inception-train", "--classes", "10")

    def test_autoencoder_train(self):
        self._run("autoencoder-train")

    def test_rnn_train(self):
        self._run("rnn-train", "--vocab", "50", "--seq-len", "12")

    def test_resnet_imagenet_recipe(self):
        """The published warmup recipe wiring (models/resnet/README.md:
        131-149) runs on the synthetic stand-in."""
        self._run("resnet-imagenet-train")

    def test_resnet_imagenet_recipe_perf_flags(self):
        """--fused/--remat/--s2d select the measured-on-chip perf variants
        without changing the recipe."""
        self._run("resnet-imagenet-train", "--fused", "--remat", "--s2d")


class TestPysparkModelShims:
    """bigdl.models.* reference import paths delegate to the native zoo."""

    def test_lenet_builder(self):
        import jax
        import jax.numpy as jnp

        from bigdl.models.lenet.lenet5 import build_model

        m = build_model(10)
        m.build(jax.ShapeDtypeStruct((2, 28 * 28), jnp.float32))
        assert m.forward(jnp.zeros((2, 28 * 28), jnp.float32)).shape == (2, 10)

    def test_textclassifier_builders(self):
        import jax
        import jax.numpy as jnp

        from bigdl.models.textclassifier.textclassifier import build_model

        for kind in ("cnn", "lstm", "gru"):
            m = build_model(5, model_type=kind, embedding_dim=16,
                            sequence_len=12)
            m.build(jax.ShapeDtypeStruct((2, 12, 16), jnp.float32))
            out = m.forward(jnp.zeros((2, 12, 16), jnp.float32))
            assert out.shape == (2, 5), kind

    @pytest.mark.slow
    def test_inception_v1_aux_heads(self):
        # slow tier (ISSUE-9 re-tier): a ~24s full InceptionV1 build +
        # forward; the cheap shim siblings (lenet/textclassifier) stay
        # tier-1 and the caffe-import tests cover the inception graph
        import jax
        import jax.numpy as jnp

        from bigdl.models.inception.inception import inception_v1

        m = inception_v1(7)
        m.build(jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))
        out = m.forward(jnp.zeros((1, 224, 224, 3), jnp.float32))
        # [main, aux2, aux1] heads concatenated along the class axis
        assert out.shape == (1, 21)
