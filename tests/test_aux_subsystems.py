"""Aux subsystems: math reward parser, dataset loader, saver/evaluator,
recover dump/load, launcher process management."""

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from areal_tpu.reward import math_parser


class TestMathParser:
    def test_boxed_extraction(self):
        assert math_parser.extract_boxed(r"so \boxed{42}") == "42"
        assert math_parser.extract_boxed(r"\boxed{\frac{1}{2}}") == r"\frac{1}{2}"
        assert math_parser.extract_boxed(r"\boxed{a} then \boxed{b}") == "b"
        assert math_parser.extract_boxed("no box") is None

    def test_gsm8k_extraction(self):
        assert math_parser.extract_answer("steps...\n#### 72") == "72"
        assert math_parser.extract_answer("the result is 3.5 meters") == "3.5"

    def test_equivalence(self):
        assert math_parser.answers_equal("72", "72.0")
        assert math_parser.answers_equal("1,234", "1234")
        assert math_parser.answers_equal("$18", "18")
        assert math_parser.answers_equal("50%", "50")
        assert math_parser.answers_equal(r"\frac{1}{2}", "0.5")
        assert not math_parser.answers_equal("71", "72")
        assert math_parser.answers_equal("1/2", "2/4")

    def test_process_results(self):
        assert math_parser.process_results("#### 10", "ten steps #### 10") == 1.0
        assert math_parser.process_results(r"answer: \boxed{10}", "#### 10") == 1.0
        assert math_parser.process_results("#### 9", "#### 10") == 0.0


class TestDataset:
    def test_gsm8k_loader_and_stateful_dataloader(self, tmp_path):
        from areal_tpu.api.cli_args import DatasetConfig
        from areal_tpu.dataset import StatefulDataLoader, get_custom_dataset
        from tests.fixtures import make_gsm8k_jsonl

        f = str(tmp_path / "train.jsonl")
        make_gsm8k_jsonl(f, n=10)
        cfg = DatasetConfig(path=f, type="gsm8k", batch_size=3)
        ds = get_custom_dataset(cfg)
        assert len(ds) == 10 and "answer" in ds[0] and "question" in ds[0]

        dl = StatefulDataLoader(ds, batch_size=3, shuffle=True, seed=1)
        assert len(dl) == 3
        seen = []
        it = iter(dl)
        seen.append(next(it))
        state = dl.state_dict()
        rest = list(it)
        # resume from the saved state reproduces the remaining batches
        dl2 = StatefulDataLoader(ds, batch_size=3, shuffle=True, seed=1)
        dl2.load_state_dict(state)
        rest2 = list(iter(dl2))
        assert [json.dumps(b) for b in rest] == [json.dumps(b) for b in rest2]
        assert dl2.epoch == 1


class TestSaverRecover:
    def _engine(self):
        from areal_tpu.api.cli_args import (
            MicroBatchSpec,
            OptimizerConfig,
            ParallelismConfig,
            TrainEngineConfig,
        )
        from areal_tpu.api.io_struct import FinetuneSpec
        from areal_tpu.engine.spmd_engine import SPMDTrainEngine
        from areal_tpu.models.config import tiny_config

        cfg = TrainEngineConfig(
            dtype="float32", param_dtype="float32",
            gradient_checkpointing=False,
            mb_spec=MicroBatchSpec(max_tokens_per_mb=4096),
            optimizer=OptimizerConfig(lr=1e-3, warmup_steps_proportion=0.0),
            parallel=ParallelismConfig(),
        )
        eng = SPMDTrainEngine(cfg)
        eng.initialize(
            ft_spec=FinetuneSpec(1, 8, 4), model_config=tiny_config(), seed=0
        )
        return eng

    def test_saver_freq_and_path(self, tmp_path):
        from areal_tpu.api.cli_args import SaverConfig
        from areal_tpu.api.io_struct import FinetuneSpec, StepInfo
        from areal_tpu.utils.saver import Saver

        eng = self._engine()
        cfg = SaverConfig(
            experiment_name="e", trial_name="t", fileroot=str(tmp_path),
            freq_steps=2,
        )
        saver = Saver(cfg, FinetuneSpec(1, 8, 4))
        s0 = StepInfo(epoch=0, epoch_step=0, global_step=0, steps_per_epoch=2)
        assert saver.save(eng, s0) is None  # freq 2: step 1 no fire
        p = saver.save(eng, s0.next())
        assert p is not None and os.path.exists(
            os.path.join(p, "model.safetensors")
        )

    def test_recover_roundtrip(self, tmp_path):
        import jax

        from areal_tpu.api.cli_args import RecoverConfig, SaverConfig
        from areal_tpu.api.io_struct import FinetuneSpec, StepInfo
        from areal_tpu.utils.recover import RecoverHandler, check_if_recover
        from areal_tpu.utils.saver import Saver
        from areal_tpu.dataset import StatefulDataLoader

        eng = self._engine()
        rcfg = RecoverConfig(mode="resume", freq_steps=1)
        handler = RecoverHandler(rcfg, str(tmp_path), "e", "t")
        saver = Saver(
            SaverConfig(experiment_name="e", trial_name="t",
                        fileroot=str(tmp_path), freq_steps=5),
            FinetuneSpec(1, 8, 4),
        )
        dl = StatefulDataLoader(list(range(8)), batch_size=2)
        next(iter(dl))
        step = StepInfo(epoch=0, epoch_step=1, global_step=1, steps_per_epoch=4)
        assert handler.dump(eng, step, saver=saver, dataloader=dl)
        assert check_if_recover(rcfg, handler.recover_root)

        eng2 = self._engine()
        dl2 = StatefulDataLoader(list(range(8)), batch_size=2)
        info = handler.load(eng2, saver=Saver(
            SaverConfig(experiment_name="e", trial_name="t",
                        fileroot=str(tmp_path), freq_steps=5),
            FinetuneSpec(1, 8, 4)), dataloader=dl2)
        assert info.last_step_info.global_step == 1
        assert dl2.state_dict() == dl.state_dict()
        p1 = jax.device_get(eng.params)
        p2 = jax.device_get(eng2.params)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b), p1, p2
        )
        # optimizer state restored too
        o1 = jax.device_get(eng.opt_state)
        o2 = jax.device_get(eng2.opt_state)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(a, b), o1, o2
        )


class TestLauncher:
    def test_submit_poll_stop(self, tmp_path):
        from areal_tpu.launcher.local import JobException, LocalLauncher

        l = LocalLauncher("e", "t", str(tmp_path))
        ok = l.submit("ok", [sys.executable, "-c", "print('hi')"])
        bad = l.submit("bad", [sys.executable, "-c", "import sys; sys.exit(3)"])
        # on the processes' own exits, not on a clock: ``stop_all`` right
        # after "bad" left used to TERM an "ok" that had not printed yet
        assert bad.wait(timeout=120) == 3 and ok.wait(timeout=120) == 0
        exc = l.poll()
        assert isinstance(exc, JobException) and exc.name == "bad"
        assert l.finished("ok") and not l.alive("bad")
        l.stop_all()
        log = os.path.join(str(tmp_path), "e", "t", "logs", "ok.log")
        assert "hi" in open(log).read()
