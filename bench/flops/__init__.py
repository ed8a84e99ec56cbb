"""FLOPs per token, one module per architecture kind."""
