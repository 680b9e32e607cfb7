"""Module protocol — the Lightning-style task abstraction, made functional.

Re-designs ``ppfleetx/core/module/basic_module.py:226-283`` and the GPT glue in
``ppfleetx/models/language_model/language_module.py``. The reference protocol
is stateful (module owns parameters, ``training_step`` mutates); here a module
is a *recipe*: it builds the flax model, initialises parameters, and exposes
pure loss functions the engine can ``jax.value_and_grad`` + ``jit`` over a
mesh. Host-side hooks (``training_step_end`` logging etc.) stay imperative.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from fleetx_tpu.utils.log import logger


class BasicModule:
    """Task protocol consumed by the engine (reference ``basic_module.py:226``).

    Subclasses implement:

    - ``get_model()``          → a flax module
    - ``training_loss(params, batch, rng, step)`` → ``(loss, metrics)`` pure fn
    - ``validation_loss(params, batch)``          → ``(loss, metrics)`` pure fn

    and may override the host-side hooks. ``batch`` is a dict of arrays whose
    leading dim is the (global) batch.
    """

    #: partition-rule registry family (``parallel/rules.py``): subclasses
    #: declare which PARTITION_RULES table shards their parameter tree;
    #: None = unknown (consumers fall back to flax logical metadata with a
    #: warning, and shardcheck refuses the config)
    spec_family: Any = None

    def __init__(self, cfg: Any):
        self.cfg = cfg
        self.model = self.get_model()
        self.nranks = jax.device_count()

    # -- construction --------------------------------------------------------
    def get_model(self):
        raise NotImplementedError

    def init_variables(self, rng: jax.Array, batch: dict) -> Any:
        """Initialise the (logically-annotated) parameter pytree."""
        raise NotImplementedError

    # -- pure functions ------------------------------------------------------
    def training_loss(self, params: Any, batch: dict, rng: jax.Array,
                      step: jax.Array) -> tuple[jax.Array, dict]:
        raise NotImplementedError

    def validation_loss(self, params: Any, batch: dict) -> tuple[jax.Array, dict]:
        raise NotImplementedError

    def predict_step(self, params: Any, batch: dict) -> Any:
        """Pure forward for ``engine.predict`` (reference ``test_step``)."""
        raise NotImplementedError

    # -- host-side hooks (reference basic_module.py:239-283) -----------------
    def pretreating_batch(self, batch: dict) -> dict:
        return batch

    def training_step_end(self, log_dict: dict) -> None:
        logger.info(
            "[train] epoch: %d, batch: %d, loss: %.9f, avg_batch_cost: %.5f sec",
            log_dict.get("epoch", 0), log_dict["batch"], log_dict["loss"],
            log_dict.get("train_cost", 0.0))

    def record_step_metrics(self, host_metrics: dict) -> None:
        """What a family counts per step beyond the loss (expert loads, a
        second loss): the step's metrics are on the host already, so a
        module writes them into the ``MetricsRegistry`` here. Nothing by
        default."""

    def validation_step_end(self, log_dict: dict) -> None:
        logger.info(
            "[eval] epoch: %d, batch: %d, loss: %.9f, avg_eval_cost: %.5f sec",
            log_dict.get("epoch", 0), log_dict["batch"], log_dict["loss"],
            log_dict.get("eval_cost", 0.0))

    def input_spec(self) -> Any:
        """Abstract input signature for export/AOT (reference ``input_spec``)."""
        return None


class LanguageModule(BasicModule):
    """Shared GPT-family glue (reference ``language_module.py:31-111``):
    token/ips metric lines and the model-size banner."""

    tokens_per_sample: int = 1024

    def flops_per_token(self) -> float | None:
        """fwd+bwd model FLOPs per trained token (for the MFU line)."""
        from fleetx_tpu.utils.hardware import gpt_flops_per_token

        c = getattr(self, "model_cfg", None)
        if c is None:
            return None
        return gpt_flops_per_token(c.num_layers, c.hidden_size,
                                   self.tokens_per_sample,
                                   vocab_size=c.vocab_size)

    def training_step_end(self, log_dict: dict) -> None:
        speed = 1.0 / max(log_dict.get("train_cost", 1e-9), 1e-9)
        default_global_tokens_num = log_dict.get(
            "global_batch_size", log_dict.get("batch_size", 1)) * self.tokens_per_sample
        mfu = ""
        fpt = self.flops_per_token()
        if fpt:
            from fleetx_tpu.utils.hardware import peak_flops

            peak = peak_flops(jax.devices()[0])
            if peak:
                util = (fpt * default_global_tokens_num * speed
                        / (peak * max(self.nranks, 1)))
                mfu = f", mfu: {util:.1%}"
        logger.info(
            "[train] global step %d, epoch: %d, batch: %d, loss: %.9f, "
            "avg_batch_cost: %.5f sec, speed: %.2f step/s, "
            "ips_total: %.0f tokens/s, ips: %.0f tokens/s, learning rate: %.5e%s",
            log_dict["global_step"], log_dict.get("epoch", 0), log_dict["batch"],
            log_dict["loss"], log_dict.get("train_cost", 0.0), speed,
            default_global_tokens_num * speed,
            default_global_tokens_num * speed / max(self.nranks, 1),
            log_dict.get("lr", 0.0), mfu)

    def validation_step_end(self, log_dict: dict) -> None:
        speed = 1.0 / max(log_dict.get("eval_cost", 1e-9), 1e-9)
        logger.info(
            "[eval] step %d, batch: %d, loss: %.9f, avg_eval_cost: %.5f sec, "
            "speed: %.2f step/s",
            log_dict.get("global_step", 0), log_dict["batch"], log_dict["loss"],
            log_dict.get("eval_cost", 0.0), speed)

    @staticmethod
    def model_size(num_layers: int, hidden_size: int, vocab_size: int) -> float:
        """Parameter-count formula in billions (reference
        ``language_module.py:102-105``)."""
        return (num_layers * (12.0 * hidden_size * hidden_size)
                + vocab_size * hidden_size) / 1e9


class GPTModule(LanguageModule):
    """GPT pretraining task (reference ``language_module.py:112-178``)."""

    @property
    def spec_family(self) -> str:
        """``gpt_moe`` when the MLP stack is mixture-of-experts, ``gpt``
        otherwise — the two families carry different MLP rule tables."""
        return "gpt_moe" if self.model_cfg.moe_num_experts > 0 else "gpt"

    def __init__(self, cfg: Any):
        from fleetx_tpu.models.gpt.model import config_from_dict

        model_cfg = dict(cfg.get("Model", cfg)) if isinstance(cfg, dict) else dict(cfg)
        if isinstance(cfg, dict):
            # pipeline topology flows from the Distributed section (reference
            # pp_degree, utils/config.py:30-65); microbatch count from the
            # engine's accumulate_steps (reference pipeline micro-batching,
            # language_module.py:155-161 + config.py:117)
            dist = dict(cfg.get("Distributed") or {})
            eng = dict(cfg.get("Engine") or {})
            pp = int(dist.get("pp_degree") or 1)
            if pp > 1 and not model_cfg.get("pp_degree"):
                model_cfg["pp_degree"] = pp
            vpp = int(dist.get("virtual_pp_degree") or 0)
            if vpp > 1 and not model_cfg.get("virtual_pp_degree"):
                model_cfg["virtual_pp_degree"] = vpp
            if int(model_cfg.get("pp_degree") or 1) > 1 and \
                    not model_cfg.get("pp_microbatches"):
                model_cfg["pp_microbatches"] = int(eng.get("accumulate_steps") or 0)
            # QAT wrap (reference language_module.py:142-144)
            quant = dict(cfg.get("Quantization") or {})
            if quant.get("enable"):
                model_cfg["use_qat"] = True
                if quant.get("weight_bits"):
                    model_cfg["qat_bits"] = int(quant["weight_bits"])
                # activation width may differ from the weight width
                # (reference paddleslim act quant config)
                if quant.get("activation_bits"):
                    model_cfg["qat_act_bits"] = int(quant["activation_bits"])
        self.model_cfg = config_from_dict(model_cfg)
        self.tokens_per_sample = self.model_cfg.max_position_embeddings
        super().__init__(cfg)
        logger.info(
            "GPT model: layers=%d hidden=%d heads=%d vocab=%d (~%.2fB params)",
            self.model_cfg.num_layers, self.model_cfg.hidden_size,
            self.model_cfg.num_attention_heads, self.model_cfg.vocab_size,
            self.model_size(self.model_cfg.num_layers, self.model_cfg.hidden_size,
                            self.model_cfg.vocab_size))

    def get_model(self):
        from fleetx_tpu.models.gpt.model import GPTForPretraining

        return GPTForPretraining(self.model_cfg)

    def init_variables(self, rng: jax.Array, batch: dict) -> Any:
        variables = self.model.init(
            {"params": rng}, batch["tokens"][:1], batch["position_ids"][:1],
            deterministic=True)
        return variables["params"]

    def training_loss(self, params, batch, rng, step):
        from flax.core import meta
        from fleetx_tpu.models.gpt.model import cross_entropy_loss

        dropout_rng = jax.random.fold_in(rng, step)
        variables = {"params": meta.unbox(params)}
        if self.model_cfg.moe_num_experts > 0:
            kwargs = {}
            if self.model_cfg.vocab_chunk:
                # the chunked LM head composes with the MoE aux collection
                kwargs = dict(labels=batch["labels"],
                              loss_mask=batch["loss_mask"])
            out, aux_vars = self.model.apply(
                variables, batch["tokens"], batch["position_ids"],
                deterministic=False, rngs={"dropout": dropout_rng},
                mutable=["losses"], **kwargs)
            loss = (out if self.model_cfg.vocab_chunk else
                    cross_entropy_loss(out, batch["labels"],
                                       batch["loss_mask"]))
            aux = sum(jnp.sum(l) for l in
                      jax.tree.leaves(aux_vars.get("losses", {})))
            if self.model_cfg.pp_degree > 1:
                # the pipeline sows one (bubble-gated) aux value per
                # microbatch per layer; average back to one batch
                # statistic, using the M pipeline_apply actually ran
                from fleetx_tpu.parallel.pipeline import (
                    effective_microbatches)

                aux = aux / effective_microbatches(
                    self.model_cfg.pp_microbatches
                    or self.model_cfg.pp_degree,
                    batch["tokens"].shape[0])
            return loss + aux, {"loss": loss, "moe_aux": aux}
        if self.model_cfg.vocab_chunk:
            # memory-efficient LM head: the model computes the masked loss
            # itself, never materialising [b, s, vocab] logits
            loss = self.model.apply(
                variables, batch["tokens"], batch["position_ids"],
                deterministic=False, rngs={"dropout": dropout_rng},
                labels=batch["labels"], loss_mask=batch["loss_mask"])
            return loss, {"loss": loss}
        logits = self.model.apply(
            variables, batch["tokens"], batch["position_ids"],
            deterministic=False, rngs={"dropout": dropout_rng})
        loss = cross_entropy_loss(logits, batch["labels"], batch["loss_mask"])
        return loss, {"loss": loss}

    def validation_loss(self, params, batch):
        from flax.core import meta
        from fleetx_tpu.models.gpt.model import cross_entropy_loss

        variables = {"params": meta.unbox(params)}
        if self.model_cfg.vocab_chunk:
            loss = self.model.apply(
                variables, batch["tokens"], batch["position_ids"],
                deterministic=True, labels=batch["labels"],
                loss_mask=batch["loss_mask"])
            return loss, {"loss": loss}
        logits = self.model.apply(
            variables, batch["tokens"], batch["position_ids"],
            deterministic=True)
        loss = cross_entropy_loss(logits, batch["labels"], batch["loss_mask"])
        return loss, {"loss": loss}

    def predict_step(self, params, batch):
        """Forward logits (reference ``test_step``/predict loop)."""
        from flax.core import meta

        return self.model.apply(
            {"params": meta.unbox(params)}, batch["tokens"],
            batch.get("position_ids"), deterministic=True)

    def input_spec(self):
        s = self.model_cfg.max_position_embeddings
        return {
            "tokens": jax.ShapeDtypeStruct((1, s), jnp.int32),
            "position_ids": jax.ShapeDtypeStruct((1, s), jnp.int32),
        }


class GPTEvalModule(GPTModule):
    """Offline eval task: WikiText perplexity / LAMBADA accuracy
    (reference ``GPTEvalModule``, ``language_module.py:277-389``)."""

    def __init__(self, cfg: Any):
        ev = dict(cfg.get("Offline_Eval") or {}) if isinstance(cfg, dict) else {}
        self.eval_type = ev.get("eval_type", "ppl")  # ppl | acc
        super().__init__(cfg)

    def batch_metrics(self, params, batch):
        """Pure per-batch sums the host aggregates (jit-able)."""
        from flax.core import meta
        from fleetx_tpu.models.gpt.model import cross_entropy_per_token

        logits = self.model.apply(
            {"params": meta.unbox(params)}, batch["tokens"],
            batch["position_ids"], deterministic=True)
        losses = cross_entropy_per_token(logits, batch["labels"])
        mask = batch["loss_mask"].astype(jnp.float32)
        preds = jnp.argmax(logits, axis=-1)
        tok_correct = jnp.where(mask > 0, preds == batch["labels"], True)
        row_has_target = mask.sum(axis=1) > 0
        row_correct = jnp.all(tok_correct, axis=1) & row_has_target
        return {
            "loss_sum": (losses * mask).sum(),
            "token_count": mask.sum(),
            "correct": row_correct.sum(),
            "rows": row_has_target.sum(),
        }

    def run_offline_eval(self, params, data_loader) -> dict:
        """Aggregate PPL / accuracy over a loader
        (reference ``validation_epoch_end``, ``language_module.py:352-389``)."""
        import numpy as np

        fn = jax.jit(self.batch_metrics)
        totals = {"loss_sum": 0.0, "token_count": 0.0, "correct": 0.0, "rows": 0.0}
        for batch in data_loader:
            out = jax.device_get(fn(params, batch))
            for k in totals:
                totals[k] += float(out[k])
        results: dict = dict(totals)
        if totals["token_count"]:
            avg = totals["loss_sum"] / totals["token_count"]
            results["loss"] = avg
            results["ppl"] = float(np.exp(min(avg, 30.0)))
        if self.eval_type == "acc" and totals["rows"]:
            results["acc"] = totals["correct"] / totals["rows"]
        logger.info("[eval] offline results: %s",
                    {k: round(v, 6) for k, v in results.items()})
        return results


class GPTGenerationModule(GPTModule):
    """Text-generation task (reference ``GPTGenerationModule``,
    ``language_module.py:179-271``): wraps the jitted sampling loop with
    tokenize / left-pad / detokenize host glue."""

    def __init__(self, cfg: Any):
        from fleetx_tpu.models.gpt.generation import GenerationConfig

        gen = dict(cfg.get("Generation") or {}) if isinstance(cfg, dict) else {}
        # reference decode_strategy: "sampling" | "greedy_search" (the
        # reference raises on greedy; here it is supported); the older
        # use_topp_sampling flag is honoured when no strategy is given
        strategy = gen.get("decode_strategy")
        if strategy is not None:
            assert strategy in ("sampling", "greedy_search", "beam_search"), \
                strategy
            do_sample = strategy == "sampling"
        else:
            do_sample = bool(gen.get("use_topp_sampling", True))
        self.use_beam_search = strategy == "beam_search"
        self.gen_cfg = GenerationConfig(
            max_new_tokens=int(gen.get("max_dec_len", 64)),
            min_new_tokens=int(gen.get("min_dec_len", 0)),
            temperature=float(gen.get("temperature", 1.0)),
            top_k=int(gen.get("top_k", 0)),
            top_p=float(gen.get("top_p", 0.0)),
            repetition_penalty=float(gen.get("repetition_penalty", 1.0)),
            do_sample=do_sample,
            num_return_sequences=int(gen.get("num_return_sequences", 1)),
            eos_token_id=int(gen.get("eos_token_id", 50256)),
            pad_token_id=int(gen.get("pad_token_id", 50256)),
            # diverse beam knobs (reference hybrid_model.py:990-1004)
            num_beams=int(gen.get("num_beams", 1)),
            num_beam_groups=int(gen.get("num_beam_groups", 1)),
            diversity_rate=float(gen.get("diversity_rate", 0.0)),
            length_penalty=float(gen.get("length_penalty", 0.0)),
        )
        if self.use_beam_search:
            assert self.gen_cfg.num_return_sequences <= self.gen_cfg.num_beams
        self.tokenizer = None
        super().__init__(cfg)

    def generate_ids(self, params: Any, prompts: list, rng: jax.Array):
        """prompts: list of token-id lists →
        ``[len(prompts) * num_return_sequences, max_new_tokens]`` numpy,
        prompt-major (rows ``i*n .. i*n+n-1`` continue prompt ``i``)."""
        from flax.core import meta
        from fleetx_tpu.models.gpt import generation as G

        tokens, mask = G.left_pad(prompts, self.gen_cfg.pad_token_id)
        if getattr(self, "use_beam_search", False):
            seqs, _ = G.beam_search(self.model, meta.unbox(params),
                                    self.gen_cfg, jnp.asarray(tokens),
                                    jnp.asarray(mask))
            # beams come back best-first per prompt: keep the top
            # num_return_sequences rows of each prompt's num_beams block
            nb, nr = self.gen_cfg.num_beams, self.gen_cfg.num_return_sequences
            seqs = seqs.reshape(len(prompts), nb, -1)[:, :nr]
            return jax.device_get(seqs.reshape(len(prompts) * nr, -1))
        out = G.generate(self.model, meta.unbox(params), self.gen_cfg,
                         jnp.asarray(tokens), jnp.asarray(mask), rng)
        return jax.device_get(out)

    def generate(self, params: Any, texts: list[str], rng: jax.Array) -> list[str]:
        assert self.tokenizer is not None, "set module.tokenizer first"
        prompts = [self.tokenizer.encode(t) for t in texts]
        out = self.generate_ids(params, prompts, rng)
        eos = self.gen_cfg.eos_token_id
        results = []
        for row in out:
            ids = [int(t) for t in row]
            if eos in ids:
                ids = ids[:ids.index(eos)]
            results.append(self.tokenizer.decode(ids))
        return results
