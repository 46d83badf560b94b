# Sharded execution: logical-axis rules + DP×TP(×EP) mesh builder
# (sharding), tensor-parallel quantized matmul (tp), expert-parallel
# quantized einsum and MoE layer (ep).
