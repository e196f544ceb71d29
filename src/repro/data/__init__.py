from .synthetic import (
    regression_dataset,
    DATASET_SPECS,
    mnist_like_two_digits,
)
