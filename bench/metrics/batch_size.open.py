"""batch_size.open: Requests per batch, as the Server's responses count them."""
from bench import readers


def read(m):
    return readers.batch_size(m)
