"""Moonlight-16B-A3B prefill as a user runs it: the system under test.

It builds ``mpi4jax_tpu.models.moonlight`` through its user entry, with
every option at its default: ``Moonlight(MoonlightConfig.from_hf(config),
mesh)`` over the cell's chips on one ``ep`` axis, ``init_params`` from the
configuration's ``weight_seed`` (generated on the chips), and the jitted
``prefill_fn``.  A solve is one ``("prefill", 1)`` call: one step that
prefills one prompt per chip.  The program receives only the seeded
prompt batches.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from mpi4jax_tpu.models import moonlight
from perf import schedule
from perf.references import moonlight as reference

# the program's named scopes: a traced run's reduction gives each
# instruction the innermost of them in its op_name
SCOPES = moonlight.SCOPES


def prompts(seed, traffic, config, chips):
    """``traffic["batches"]`` prompt batches ``(chips * prompts_per_chip,
    prompt_tokens)`` of int32 ids: Zipf(``zipf_s``) ranks over the
    vocabulary, mapped to ids by a permutation fixed by the
    configuration's ``weight_seed``."""
    vocab = config["vocab_size"]
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -traffic["zipf_s"]
    p /= p.sum()
    ids = schedule.rng(config["weight_seed"], 2).permutation(vocab)
    gen = schedule.rng(seed, 2)
    shape = (chips * traffic["prompts_per_chip"], traffic["prompt_tokens"])
    return [ids[gen.choice(vocab, size=shape, p=p)].astype(np.int32)
            for _ in range(traffic["batches"])]


class Cell:
    """One configuration under one traffic mix, ready to be driven."""

    def __init__(self, config, traffic, devices, seed):
        self.config = config
        chips = config["ep"]
        self.mesh = Mesh(np.array(devices[:chips]), ("ep",))
        self.devices = list(self.mesh.devices.flat)
        self.cfg = moonlight.MoonlightConfig.from_hf(config)
        self.model = moonlight.Moonlight(self.cfg, self.mesh)
        self.prefill = self.model.prefill_fn()
        self.calls = [("prefill", 1)]
        self.tokens = prompts(seed, traffic, config, chips)
        self.inputs = []
        self.params = None
        self._counts = []

    # -- set-up -------------------------------------------------------------
    def generate(self):
        """The weights, generated on the chips, and the prompt batches."""
        self.params = self.model.init_params(self.config["weight_seed"])
        sharding = NamedSharding(self.mesh, P("ep"))
        self.inputs = [jax.device_put(t, sharding) for t in self.tokens]
        jax.block_until_ready((self.params, self.inputs))

    def warm_up(self):
        """Compile and run the prefill once."""
        jax.block_until_ready(self.call("prefill", self.inputs[0]))
        self._counts = []

    # -- the timed path -----------------------------------------------------
    def initial(self, slot):
        return self.inputs[slot]

    def call(self, kind, state):
        out = self.prefill(self.params, state)
        self._counts.append(out[2])
        return out

    @staticmethod
    def result(state):
        """The final normed hidden states and last-token logits (kept on
        the device)."""
        return state[:2]

    def counters(self):
        """Per prefill step, from the per-expert row counts each MoE layer
        computes for its exchange: ``moe.rows`` (rows each chip's experts
        received, over all MoE layers, in chip order), ``moe.max_expert_
        rows`` (the most rows one expert received in one layer),
        ``moe.offchip_bytes`` (bytes each chip's dispatch and combine sent
        to other chips), ``tokens``, and the ``shape`` that
        ``perf/work/moonlight.py`` counts work from."""
        c = np.stack([np.asarray(a) for a in self._counts]).astype(np.int64)
        steps, chips = c.shape[0], c.shape[1]
        local = self.cfg.n_routed_experts // chips
        # sent[i, j]: rows chip i sent chip j, over layers and steps
        sent = c.reshape(steps, chips, -1, chips, local).sum(axis=(0, 2, 4))
        row_bytes = self.cfg.hidden_size * np.dtype(self.cfg.dtype).itemsize
        off = sent - np.diag(np.diag(sent))
        return {
            "moe.rows": (sent.sum(axis=0) / steps).tolist(),
            "moe.max_expert_rows": int(c.sum(axis=1).max()),
            "moe.offchip_bytes": ((off.sum(axis=1) + off.sum(axis=0))
                                  * row_bytes / steps).tolist(),
            "tokens": int(self.tokens[0].size),
            "steps": steps,
            "device_ids": [d.id for d in self.devices],
            "shape": self.shape(),
        }

    def shape(self):
        cfg = self.cfg
        return {
            "hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "qk_head": cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
            "v_head": cfg.v_head_dim, "moe_width": cfg.moe_intermediate_size,
            "experts_per_chip": cfg.n_routed_experts // len(self.devices),
            "moe_layers": cfg.n_moe_layers, "layers": cfg.num_hidden_layers,
            "seq_len": self.tokens[0].shape[1],
            "seqs_per_chip": self.tokens[0].shape[0] // len(self.devices),
            "chips": len(self.devices), "vocab": cfg.vocab_size,
            "itemsize": np.dtype(cfg.dtype).itemsize,
            "active_params": active_params(cfg),
        }

    # -- the check ----------------------------------------------------------
    @staticmethod
    def to_host(result):
        hidden, logits = result
        return {"hidden": np.asarray(hidden, np.float32),
                "logits": np.asarray(logits, np.float32)}

    def free(self):
        self.params = None
        self.inputs = []
        self.prefill = None

    def reference(self, slot, dtype, layers=None):
        """The plain reference's answer for the prompt batch ``slot``."""
        with jax.default_device(self.devices[0]):
            hidden, logits = reference.forward(self.config, self.tokens[slot],
                                               dtype, layers)
        return {"hidden": hidden, "logits": logits}


def active_params(cfg):
    """Parameters one token's forward pass multiplies by: every layer's
    attention and norms, the dense FFNs, and in each MoE layer the router,
    the shared experts and ``num_experts_per_tok`` routed experts; not the
    embedding (a lookup) or the head (last tokens only)."""
    one = moonlight.parameter_count
    full = one(cfg, cfg.num_experts_per_tok)
    top = 2 * cfg.vocab_size * cfg.hidden_size + cfg.hidden_size
    return full - top
