"""dml_cnn_cifar10_tpu benchmark (BENCHMARK.json at the root of the repo)."""
