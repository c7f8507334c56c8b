SELECT count(*) c, sum(l_extendedprice) s FROM lineitem WHERE l_orderkey = ?
