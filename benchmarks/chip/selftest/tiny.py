"""Cells cut to a size a CPU test run holds: the real configuration and
traffic files with every width shrunk, for driving the harness off the chip."""
from __future__ import annotations

import copy

import harness

SMALL = {
    'llama': {'hidden_size': 64, 'intermediate_size': 128, 'num_attention_heads': 4,
              'num_key_value_heads': 2, 'head_dim': 16, 'vocab_size': 256},
    'rwkv6': {'hidden_size': 128, 'attention_hidden_size': 128, 'intermediate_size': 256,
              'vocab_size': 256, 'num_hidden_layers': 2},
}


def tiny_cell(workload: str, seq: int = 16, batch: int = 4, outer_every: int = 3,
              widths: dict | None = None, cell: harness.Cell | None = None) -> harness.Cell:
    cell = cell or harness.load_cell(workload)
    conf = copy.deepcopy(cell.config)
    conf.update(widths or SMALL[conf['family']])
    keys = conf['program']['keys']
    conf['program']['model_config'].update(
        {attr: conf[key] for key, attr in keys.items()})
    conf['init'] = [r if r[0] != '^unembed/table$' else [r[0], 'normal', conf['hidden_size'] ** -0.5]
                    for r in conf['init']]
    traffic = dict(cell.traffic, seq=seq, batch=batch, outer_every=outer_every)
    return harness.Cell(name=cell.name, chips=1, config=conf, traffic=traffic,
                        limits=dict(cell.limits), per_layer=cell.per_layer)
