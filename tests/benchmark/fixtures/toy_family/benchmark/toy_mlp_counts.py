"""Operations and bytes of the toy regressor, each `fn(config, spec)`: the
family's keys sit at the top level of its configurations' files."""


def _layer_sizes(config):
    widths = [config["input_size"]] + [config["hidden_size"]] * config["num_hidden_layers"] + [1]
    return list(zip(widths[:-1], widths[1:]))


def train_flops_per_sample(config, spec):
    """Forward and backward of one row: three forwards, two operations a
    weight."""
    return 3 * sum(2 * cin * cout for cin, cout in _layer_sizes(config))


def dense_flops_per_call(config, spec):
    """One hidden layer's product over a batch."""
    return 2 * spec["batch"] * config["hidden_size"] * config["hidden_size"]


def dense_bytes_per_call(config, spec):
    """Its weights, its input and its output, in float32."""
    return 4 * (config["hidden_size"] * config["hidden_size"] + 2 * spec["batch"] * config["hidden_size"])
