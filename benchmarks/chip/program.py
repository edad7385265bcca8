"""The system under test, as the benchmark reaches it: a configuration
file turned into the program's ``CSNNConfig`` and its analytic plan.

Queues are sized to the whole feature map (lossless: no event is ever
dropped), ``channel_block`` comes from the configuration file, and the
analytic plan picks each layer's variant, as the serving launcher does.
"""
from __future__ import annotations


def csnn_config(cfg: dict, c_in: int):
    from repro.core.csnn import CSNNConfig, ConvSpec, FCSpec
    layers = tuple(
        ConvSpec(lay["conv"], kernel=lay.get("kernel", 3),
                 pool=lay.get("pool")) if "conv" in lay
        else FCSpec(lay["fc"]) for lay in cfg["layers"])
    return CSNNConfig(input_hw=tuple(cfg["input_hw"]), input_channels=c_in,
                      layers=layers, t_steps=cfg["t_steps"], v_t=cfg["v_t"],
                      relu_clamp=cfg["relu_clamp"])


def plan(net, cfg: dict, batch: int, *, ingest: bool = False):
    from repro.core.plan import plan_network
    h, w = net.input_hw
    return plan_network(net, capacity=h * w,
                        channel_block=cfg["plan"]["channel_block"],
                        batch_tile=batch, event_par=None, ingest=ingest)
